"""Serving engine with a RISP-guided KV-prefix cache (beyond-paper feature).

The thesis' Ch. 2.5 contrasts its persistent intermediate-data store with
web-service caching; this module closes the loop for LLM serving: a request's
prompt is a *workflow* — the token stream chunked into fixed-size modules —
and RISP's association mining over the request history decides which prefix
KV states to retain.  High-confidence shared prefixes (system prompts,
few-shot preambles) get their KV snapshot stored; later requests skip
prefilling them.  This is RadixAttention-style prefix caching with a
*mined admission policy* instead of cache-everything + LRU.

Snapshots live in a :class:`~repro.serve.snapshots.FabricSnapshotStore` on
a storage backend: a bare engine gets one over an in-process
``MemoryBackend``, while ``Client.serve_engine`` mounts one on the shared
artifact fabric so N serving processes reuse each other's prefills.  On the
fabric, prefill itself becomes a *coordinated compute*: when a ``flight``
(:class:`~repro.net.flight.DistributedSingleFlight`) is wired, exactly one
engine fleet-wide prefills a shared prefix (the leader stores the snapshot;
followers block on the lease, then load it) — the same exactly-once
discipline the workflow scheduler applies to module computes.
"""
from __future__ import annotations

import hashlib
import time
from dataclasses import dataclass, field
from typing import Any

import numpy as np

import jax
import jax.numpy as jnp

from ..configs.base import LMConfig
from ..core.backends import MemoryBackend
from ..core.registry import ModuleRegistry
from ..core.risp import RISP, StoragePolicy, StoredRecord
from ..core.workflow import ModuleRef, Workflow
from ..models import transformer
from ..obs import tracing as _tracing
from ..obs.metrics import MetricsRegistry
from ..sched.singleflight import SingleFlight
from ..sched.stats import AggregateStats
from .snapshots import FabricSnapshotStore


def _chunk_id(tokens: np.ndarray) -> str:
    return hashlib.sha1(np.ascontiguousarray(tokens).tobytes()).hexdigest()[:12]


@dataclass
class GenStats:
    prompt_len: int
    n_chunks: int
    chunks_skipped: int
    prefill_s: float
    decode_s: float
    stored_prefixes: int
    n_new_tokens: int
    # entry to ``generate()`` until the first new token is known (the whole
    # call where none is asked for)
    first_token_s: float = 0.0


class ServeMetrics:
    """The canonical ``repro_serve_*`` instruments.

    One home for every serving counter; :meth:`ServeEngine.aggregate_stats`
    is reconstructed from these (the legacy ``AggregateStats`` shape survives
    as an alias — see ``obs/naming.py::ALIASES``).
    """

    def __init__(self, registry: MetricsRegistry) -> None:
        m = registry
        self.requests = m.counter(
            "repro_serve_requests_total", "generation requests served"
        )
        self.chunks = m.counter(
            "repro_serve_chunks_total", "prompt chunks across all requests"
        )
        self.chunks_skipped = m.counter(
            "repro_serve_chunks_skipped_total",
            "prompt chunks skipped by restoring a KV snapshot",
        )
        self.tokens = m.counter("repro_serve_tokens_total", "new tokens decoded")
        self.stored = m.counter(
            "repro_serve_snapshots_stored_total",
            "KV snapshot admissions by this engine",
        )
        self.busy = m.counter(
            "repro_serve_busy_seconds_total", "seconds spent prefilling + decoding"
        )
        self.saved = m.counter(
            "repro_serve_prefill_saved_seconds_total",
            "prefill seconds avoided by snapshot reuse (measured cost minus load)",
        )
        self.expert_assignments = m.counter(
            "repro_serve_expert_assignments_total",
            "token-to-expert assignments routed (top-k per token per MoE layer)",
        )
        self.expert_held = m.counter(
            "repro_serve_expert_held_assignments_total",
            "routed assignments that went to the experts this engine holds",
        )


@dataclass
class _PrefixResult:
    """State after materializing a prompt prefix (the coordinated unit)."""

    cache: Any  # device cache pytree at ``depth`` chunks
    cache_len: Any  # jnp [1] int32
    depth: int  # chunks materialized in ``cache``
    logits: Any | None  # logits of the last prefilled chunk (None: none ran)
    skipped: int  # chunks restored from a snapshot instead of prefilled
    stored: int  # snapshot admissions performed
    prefill_s: float  # wall seconds of prefill done here
    routed: Any = None  # route totals of the steps run here (MoE), on the device


@dataclass
class ServeEngine:
    cfg: LMConfig
    params: Any
    max_len: int = 512
    chunk: int = 32
    policy: StoragePolicy = field(default_factory=RISP)
    greedy: bool = True
    # optional shared ModuleRegistry: observed prompt chunks are recorded as
    # (non-executable) modules with prefill-cost hints, so the serving
    # workload's module universe is introspectable through the same registry
    # the workflow engines consume (repro.api.Client wires one across all
    # front doors)
    registry: ModuleRegistry | None = None
    # where snapshots live (None -> an engine-private store over a
    # MemoryBackend, unbounded); pass one over a shared backend to share
    # prefills across processes, with ``capacity_bytes`` to bound it
    snapshots: FabricSnapshotStore | None = None
    # single-flight election over shared-prefix prefills; a
    # DistributedSingleFlight makes the election fleet-wide
    flight: SingleFlight | None = None
    metrics: MetricsRegistry | None = None
    # dataset identity of the prompt workflows (Client.serve_engine composes
    # its namespace in, so snapshot keys are tenant-scoped like any artifact)
    dataset_id: str = "prompts"

    def __post_init__(self) -> None:
        if self.metrics is None:
            self.metrics = (
                self.snapshots.metrics
                if self.snapshots is not None
                else MetricsRegistry()
            )
        if self.snapshots is None:
            self.snapshots = FabricSnapshotStore(MemoryBackend(), registry=self.metrics)
        # every removal path (budget eviction, fleet event, phantom probe)
        # funnels through the store's listeners: the policy's claim of the
        # snapshot dies with the snapshot — never the other way around.
        # GIL-atomic pop without the policy lock (documented lock order).
        self.snapshots.add_evict_listener(
            lambda key: self.policy.stored.pop(key, None)
        )
        self._sm = ServeMetrics(self.metrics)
        self._chunk_prefill_s = 0.0  # EMA seconds to prefill one chunk
        self._t_first: float | None = None
        self._t_last = 0.0
        cfg = self.cfg

        if cfg.moe is not None:
            held = cfg.moe.held

            def totals(n, hits):
                return n + jnp.stack([hits.sum(), hits[:, :held].sum()])

        def program(step):
            """``step`` jitted under its own name, so a profiler shows
            ``jit_prefill_chunk`` / ``jit_decode_step``.  With MoE each call
            also adds its route totals [assignments, held] to the request's
            running pair on the device, ``n``, and hands back its per-layer
            counts; the pair is read once per request."""
            if cfg.moe is None:

                def fn(p, t, c, l):
                    return step(p, cfg, t, c, l)

            else:

                def fn(p, t, c, l, n):
                    logits, c, l, hits = step(p, cfg, t, c, l, route_stats=True)
                    return logits, c, l, totals(n, hits), hits

            fn.__name__ = fn.__qualname__ = step.__name__
            return jax.jit(fn)

        self._prefill = program(transformer.prefill_chunk)
        self._decode = program(transformer.decode_step)

    def _new_routed(self):
        """A request's route totals [assignments, held]: None without MoE."""
        return None if self.cfg.moe is None else jnp.zeros(2, jnp.int32)

    def _step(self, fn, tok, cache, cache_len, routed):
        """One call of a step program: (logits, cache, cache_len, routed)."""
        if routed is None:
            return (*fn(self.params, tok, cache, cache_len), None)
        logits, cache, cache_len, routed, _ = fn(self.params, tok, cache, cache_len, routed)
        return logits, cache, cache_len, routed

    # -- RISP bookkeeping over request chunks ------------------------------
    def _workflow(self, chunks: list[np.ndarray]) -> Workflow:
        mods = tuple(ModuleRef(_chunk_id(c)) for c in chunks)
        if self.registry is not None:
            for m in mods:
                self.registry.ensure(
                    m.module_id, cost_hint=self._chunk_prefill_s or None
                )
        return Workflow(
            self.dataset_id, mods, workflow_id=f"req{self.policy.n_pipelines}"
        )

    def _load_snapshot(self, key: str, depth: int) -> "Any | None":
        """Restore one snapshot, crediting the measured time it saved."""
        with _tracing.span("serve.snapshot.load", kind="serve", key=key) as sp:
            snap = self.snapshots.load(key)
            if snap is None:
                sp.set(status="miss")
                return None
            recompute = snap.prefill_s or depth * self._chunk_prefill_s
            saved = max(recompute - snap.load_s, 0.0)
            sp.set(source="snapshot", saved_s=round(saved, 6), depth=depth)
            self._sm.saved.inc(saved)
        return snap

    # -- prefill -------------------------------------------------------------
    def _prefill_prefix(
        self,
        chunks: list[np.ndarray],
        wf: Workflow,
        rec: Any,
        depth_keys: dict[int, str],
        upto: int,
        presence: "dict[str, bool | None] | None" = None,
    ) -> _PrefixResult:
        """Materialize the first ``upto`` chunks: restore the deepest live
        snapshot, prefill the rest, store what the policy admitted.

        This is the unit a single-flight leader runs.  A follower re-running
        it after the leader finishes re-probes and finds the leader's
        snapshot — so it prefills nothing (the exactly-once property)."""
        ws = self.policy.with_state
        if presence is None:
            presence = self.snapshots.presence_many(list(depth_keys.values()))
        # authoritative absences invalidate any claim the policy still holds
        # (same discipline as the executor's probe walk; ``None`` =
        # unreachable is deliberately NOT evidence of absence) — except the
        # claims ``policy.step`` just admitted for THIS request: those are
        # pending the save below, not stale
        pending = {p.key(ws) for p in rec.store}
        for key in depth_keys.values():
            if presence.get(key) is False and key not in pending:
                self.policy.stored.pop(key, None)

        start, cache, cache_len_i = 0, None, 0
        for d in range(upto, 0, -1):
            key = depth_keys[d]
            if not presence.get(key):
                continue
            snap = self._load_snapshot(key, d)
            if snap is None:
                continue  # phantom: the store already pruned + notified
            cache = jax.tree_util.tree_map(jnp.asarray, snap.cache)
            cache_len_i = snap.length
            start = d
            # cross-process adoption: mining in this process may never have
            # admitted this prefix — record that it is stored so the policy
            # recommends reusing it next time
            self.policy.stored.setdefault(
                key, StoredRecord(wf.prefix(d), self.policy.n_pipelines)
            )
            break
        if cache is None:
            cache = transformer.init_cache(self.cfg, 1, self.max_len)

        t0 = time.perf_counter()
        # measured recompute cost of each boundary's prefix: seconds actually
        # spent this request, plus the EMA-priced skipped part — this is what
        # gain-loss eviction will charge to re-create the snapshot
        cost = start * self._chunk_prefill_s
        boundary: dict[int, tuple[Any, int, float]] = {}

        def keep(depth, cache, cache_len, dt):
            nonlocal cost
            cost += dt
            boundary[depth] = (cache, int(cache_len[0]), cost)

        logits, cache, cache_len, routed = self._prefill_chunks(
            chunks, start, upto, None, cache, jnp.asarray([cache_len_i], jnp.int32),
            self._new_routed(), on_chunk=keep,
        )
        prefill_s = time.perf_counter() - t0

        # store admitted prefixes (only those whose boundary we computed)
        stored = 0
        for prefix in rec.store:
            if prefix.depth > upto:
                continue  # a later (uncoordinated) stage never stores
            key = prefix.key(ws)
            if prefix.depth in boundary:
                c, ln, c_s = boundary[prefix.depth]
                with _tracing.span("serve.snapshot.save", kind="serve", key=key):
                    saved = self.snapshots.save(
                        key, c, ln, prefill_s=c_s, prefix=prefix
                    )
                if saved:
                    stored += 1
                else:  # budget (or fabric) rejected the snapshot
                    self.policy.stored.pop(key, None)
            elif presence.get(key):
                pass  # inside the restored region: already on the store
            else:
                self.policy.stored.pop(key, None)
        return _PrefixResult(
            cache=cache,
            cache_len=cache_len,
            depth=upto,
            logits=logits,
            skipped=start,
            stored=stored,
            prefill_s=prefill_s,
            routed=routed,
        )

    def _prefill_chunks(self, chunks, lo, hi, logits, cache, cache_len, routed,
                        on_chunk=None):
        """Prefill chunks [lo, hi) one step each, blocking on every step and
        folding its seconds into the per-chunk EMA.  ``on_chunk(depth, cache,
        cache_len, seconds)`` sees the state after each chunk.  Returns
        (logits, cache, cache_len, routed); ``logits`` as given when no chunk
        runs."""
        for i in range(lo, hi):
            tok = jnp.asarray(chunks[i][None], jnp.int32)
            tc = time.perf_counter()
            logits, cache, cache_len, routed = self._step(
                self._prefill, tok, cache, cache_len, routed)
            jax.block_until_ready(logits)
            dt = time.perf_counter() - tc
            self._chunk_prefill_s = (
                dt if not self._chunk_prefill_s
                else 0.3 * dt + 0.7 * self._chunk_prefill_s
            )
            if on_chunk is not None:
                on_chunk(i + 1, cache, cache_len, dt)
        return logits, cache, cache_len, routed

    # -- generation ---------------------------------------------------------
    def generate(
        self, prompt: list[int] | np.ndarray, max_new_tokens: int = 16
    ) -> tuple[list[int], GenStats]:
        t_in = time.perf_counter()
        prompt = np.asarray(prompt, np.int32)
        pad = (-len(prompt)) % self.chunk
        if len(prompt) + pad + max_new_tokens > self.max_len:
            raise ValueError(
                f"request needs {len(prompt) + pad} prompt rows (left-padded to "
                f"whole chunks of {self.chunk}) + {max_new_tokens} new tokens, "
                f"more than max_len={self.max_len}"
            )
        with _tracing.span("serve.plan", kind="serve"):
            padded = np.concatenate([np.zeros(pad, np.int32), prompt])  # left-pad
            chunks = [
                padded[i : i + self.chunk] for i in range(0, len(padded), self.chunk)
            ]
            n = len(chunks)
            wf = self._workflow(chunks)
            rec = self.policy.step(wf)
            ws = self.policy.with_state
            depth_keys = {d: wf.prefix(d).key(ws) for d in range(1, n + 1)}

        # the coordination unit: the deepest prefix this request was asked to
        # store — fleet-wide, exactly one engine should prefill it
        coord_depth = max((p.depth for p in rec.store), default=0)

        with _tracing.span("serve.prefill", kind="serve") as sp:
            t_pf = time.perf_counter()
            if self.flight is not None and coord_depth > 0:
                value, leader = self.flight.run(
                    depth_keys[coord_depth],
                    lambda: self._prefill_prefix(
                        chunks, wf, rec, depth_keys, upto=coord_depth
                    ),
                )
                if not leader:
                    # coalesced in-process behind the leader: the shared
                    # prefix arrived computed — all of it counts as skipped
                    value = _PrefixResult(
                        cache=value.cache,
                        cache_len=value.cache_len,
                        depth=value.depth,
                        logits=value.logits,
                        skipped=value.depth,
                        stored=0,
                        prefill_s=0.0,
                        routed=self._new_routed(),  # the leader counted its steps
                    )
            else:
                value = self._prefill_prefix(chunks, wf, rec, depth_keys, upto=n)

            # uncoordinated remainder: this request's private suffix
            t_ext = time.perf_counter()
            logits, cache, cache_len, routed = self._prefill_chunks(
                chunks, value.depth, n, value.logits, value.cache, value.cache_len,
                value.routed,
            )
            prefill_s = value.prefill_s + (time.perf_counter() - t_ext)
            sp.set(n=n, skipped=value.skipped, stored=value.stored)
        stored = value.stored

        # decode
        t1 = time.perf_counter()
        out: list[int] = []
        first_token_s = None
        with _tracing.span("serve.decode", kind="serve"):
            if logits is None:
                # full-prompt hit: snapshots hold no logits, so step back one
                # chunk and run it again; its rows are overwritten in place
                tok = jnp.asarray(chunks[-1][None], jnp.int32)
                logits, cache, cache_len, routed = self._step(
                    self._prefill, tok, cache, cache_len - self.chunk, routed
                )
            for _ in range(max_new_tokens):
                nxt = int(jnp.argmax(logits[0]))
                if first_token_s is None:
                    first_token_s = time.perf_counter() - t_in
                out.append(nxt)
                tok = jnp.asarray([[nxt]], jnp.int32)
                logits, cache, cache_len, routed = self._step(
                    self._decode, tok, cache, cache_len, routed
                )
            if routed is not None:
                routed = np.asarray(routed)  # read once, after the last step
        decode_s = time.perf_counter() - t1

        stats = GenStats(
            prompt_len=len(prompt),
            n_chunks=n,
            chunks_skipped=value.skipped,
            prefill_s=prefill_s,
            decode_s=decode_s,
            stored_prefixes=stored,
            n_new_tokens=len(out),
            first_token_s=(
                first_token_s if first_token_s is not None
                else time.perf_counter() - t_in
            ),
        )
        if self._t_first is None:
            self._t_first = t_pf
        self._t_last = time.perf_counter()
        m = self._sm
        m.requests.inc()
        m.chunks.inc(stats.n_chunks)
        m.chunks_skipped.inc(stats.chunks_skipped)
        m.tokens.inc(stats.n_new_tokens)
        m.stored.inc(stats.stored_prefixes)
        m.busy.inc(stats.prefill_s + stats.decode_s)
        if routed is not None:
            m.expert_assignments.inc(int(routed[0]))
            m.expert_held.inc(int(routed[1]))
        return out, stats

    # -- accounting -----------------------------------------------------------
    @property
    def n_snapshots(self) -> int:
        return self.snapshots.n_snapshots

    @property
    def n_snapshot_evictions(self) -> int:
        return self.snapshots.n_evictions

    def snapshot_bytes(self) -> int:
        return self.snapshots.snapshot_bytes()

    def aggregate_stats(self) -> AggregateStats:
        """Fleet-level view in the scheduler service's shape: one request =
        one run, one prompt chunk = one work unit (skipped = prefill reuse).
        Reconstructed from the canonical ``repro_serve_*`` registry series
        (the legacy shape is an alias — ``obs/naming.py::ALIASES``)."""
        wall = (
            (self._t_last - self._t_first)
            if self._t_first is not None and self._t_last
            else 0.0
        )
        m = self._sm
        return AggregateStats(
            runs=int(m.requests.value),
            wall_seconds=max(wall, 0.0),
            busy_seconds=m.busy.value,
            units_total=int(m.chunks.value),
            units_skipped=int(m.chunks_skipped.value),
            stored=int(m.stored.value),
        )
