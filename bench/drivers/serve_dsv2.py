"""DeepSeek-V2 at one chip's share of its experts, served through
``Client.serve_engine`` as ``serve_lm`` serves a dense model.

Before anything is drawn it checks that the program has the mechanisms the
configuration needs (leading dense layers, YaRN, group-limited routing with
a routed scale, a held share of the experts, route counts from the step
programs) and exits non-zero at once where it has not.  Set-up then draws
the weights on the device from the seed (``dsv2.program_params``), mounts
the engine on a local-directory fabric and warms its programs with one
request no timed request shares.  The window sends the mix's requests open
loop, each at its fixed time.  With ``--trace 1`` the traced window's device
time is also reduced by the program's named scopes (``mla.attend``,
``moe.route``, ``moe.experts``, ``moe.shared``), each operation's scope read
from the compiled programs' HLO metadata where the trace does not carry it,
and the step calls' route counts give the work each needed.
Afterwards a sample of the finished requests, drawn from the seed and
holding the longest, is checked against the plain float32 reference
(``dsv2.reference_logits``), with the program's state freed first; the
program's decode steps' route counts then show, token by token, where the
program chose other held experts than the reference.
"""
from __future__ import annotations

import dataclasses
import gc
import inspect
import re
import shutil
import sys
import time
from collections import defaultdict

import numpy as np

import devtrace as tr
import dsv2
import flops
import harness
import traffic
from harness import Check, CompileCounter, Outcome, annotate

_lm = harness.load_module(harness.BENCH / "drivers" / "serve_lm.py")
_sample, rows_of, served_gaps, step_calls = (
    _lm._sample, _lm.rows_of, _lm.served_gaps, _lm.step_calls)

SCOPES = ("mla.attend", "moe.route", "moe.experts", "moe.shared")
PROGRAMS = {"_prefill": "step.prefill_chunk", "_decode": "step.decode_step"}


def missing_mechanisms() -> list[str]:
    """What of the configuration the program cannot express."""
    from repro.configs import base
    from repro.models import transformer

    have = {f.name for f in dataclasses.fields(base.LMConfig)}
    missing = [f"LMConfig.{n}" for n in ("first_k_dense", "rope_scaling") if n not in have]
    moe = {f.name for f in dataclasses.fields(base.MoESpec)}
    missing += [f"MoESpec.{n}" for n in ("n_group", "topk_group", "norm_topk",
                                        "routed_scale", "held_first", "n_held")
                if n not in moe]
    for fn in (transformer.prefill_chunk, transformer.decode_step):
        if "route_stats" not in inspect.signature(fn).parameters:
            missing.append(f"{fn.__name__}(route_stats=)")
    return missing


def hlo_scopes(compiled_text: str) -> dict[str, str]:
    """Operation name -> the named scope its HLO metadata ``op_name`` lies in."""
    out = {}
    for m in re.finditer(r'^\s*(?:ROOT )?%?([\w.\-]+) = .*?op_name="([^"]*)"',
                         compiled_text, re.M):
        parts = m.group(2).split("/")
        scope = next((s for s in SCOPES if s in parts), None)
        if m.group(2).startswith("ragged-dot"):  # the grouped-matmul kernels keep no scope
            scope = "moe.experts"
        if scope:
            out[m.group(1)] = scope
    return out


def scope_times(pd, scopes: dict[str, dict[str, str]]) -> dict[str, dict[str, float]]:
    """Device seconds in the ``bench.window`` span, by program and scope
    (``other`` where no scope claims an operation).  A scope comes from the
    trace's op metadata (``tf_op``) where it has one, else from ``scopes``:
    program name -> operation -> scope."""
    spans = [s for s in tr.host_spans(pd) if s.name == tr.WINDOW_SPAN]
    lo, hi = (spans[0].start, spans[0].end) if spans else (-np.inf, np.inf)
    out: dict[str, dict[str, float]] = defaultdict(lambda: defaultdict(float))
    planes = tr.device_planes(pd)
    for plane in planes:
        modules = sorted(tr._line(plane, "XLA Modules"), key=lambda e: e.start_ns)
        starts = [m.start_ns for m in modules]
        for op in tr._line(plane, "XLA Ops"):
            s, e = op.start_ns, op.start_ns + op.duration_ns
            clipped = min(e, hi) - max(s, lo)
            name = tr._op_name(op.name)
            if clipped <= 0 or name.startswith(tr.CONTAINER_OPS):
                continue
            i = int(np.searchsorted(starts, s, side="right")) - 1
            if i < 0 or modules[i].start_ns + modules[i].duration_ns < s:
                continue
            prog = modules[i].name.split("(")[0]
            meta = dict(op.stats).get("tf_op", "")
            scope = next((x for x in SCOPES if x in str(meta).split("/")), None)
            scope = scope or scopes.get(prog, {}).get(name, "other")
            out[prog][scope] += clipped / 1e9 / len(planes)
    return {p: dict(v) for p, v in out.items()}


class ScopeTracer(harness.Tracer):
    """``harness.Tracer`` that also reduces the window by named scope."""

    def __init__(self, run, seconds: float, scopes: dict) -> None:
        super().__init__(run, seconds)
        self.scopes = scopes
        self.scope_s: dict = {}

    def stop(self) -> None:
        import jax

        if self._window is None:
            return
        self._window.__exit__(None, None, None)
        self._window = None
        jax.profiler.stop_trace()
        pd = tr.load(sorted(self.dir.rglob("*.xplane.pb"))[-1])
        self.reduction = tr.reduce_trace(pd)
        self.scope_s = scope_times(pd, self.scopes)
        shutil.rmtree(self.dir, ignore_errors=True)


def record_calls(engine, calls: list) -> None:
    """Keep each step call's route counts (a device array, read after the
    window) in ``calls`` as (kind, counts)."""
    for attr, kind in (("_prefill", "prefill"), ("_decode", "decode")):
        fn = getattr(engine, attr)

        def wrapped(*a, _fn=fn, _kind=kind):
            out = _fn(*a)
            calls.append((_kind, out[4]))
            return out

        setattr(engine, attr, wrapped)


def program_scopes(engine, chunk: int) -> dict[str, dict[str, str]]:
    """Scope of every operation of the two step programs, from their HLO
    (compiled again from the persistent cache the warm-up filled)."""
    import jax
    import jax.numpy as jnp

    from repro.models import transformer

    sds = lambda t: jax.tree_util.tree_map(  # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), t)
    params = sds(engine.params)
    cache = sds(jax.eval_shape(lambda: transformer.init_cache(engine.cfg, 1, engine.max_len)))
    n = jax.ShapeDtypeStruct((1,), jnp.int32)
    routed = jax.ShapeDtypeStruct((2,), jnp.int32)
    out = {}
    for attr, width in (("_prefill", chunk), ("_decode", 1)):
        tok = jax.ShapeDtypeStruct((1, width), jnp.int32)
        fn = getattr(engine, attr)
        text = fn.lower(params, tok, cache, n, routed).compile().as_text()
        out[f"jit_{fn.__name__}"] = hlo_scopes(text)
    return out


def useful_flops(m, traced) -> float:
    """Operations the prompt and output tokens of the requests need: pads
    and restored chunks excluded, one logit row per token read out; a
    chunk's routed work is its call's, times its share of real tokens."""
    total = 0.0
    for q, st, chunk, calls in traced:
        pad = st.n_chunks * chunk - st.prompt_len
        for (kind, n, c), hits in calls:
            if kind == "prefill":
                real = c - max(0, pad - n)
                if real <= 0:
                    continue
                last = 1 if n == (st.n_chunks - 1) * chunk else 0
                w = dsv2.step_work(m, n + c - real, real, np.zeros_like(hits), logit_rows=last)
                total += w.flops + dsv2.routed_work(m, hits).flops * real / c
            elif n < st.n_chunks * chunk + st.n_new_tokens - 1:  # the last one is read by no one
                total += dsv2.step_work(m, n, 1, hits).flops
    return total


def run(run, fault=None) -> Outcome:
    missing = missing_mechanisms()
    if missing:
        print(f"bench: the program lacks what {run.config['name']} needs: "
              f"{', '.join(missing)}", file=sys.stderr, flush=True)
        raise SystemExit(4)
    import jax
    import jax.numpy as jnp

    from repro.api import Client
    from repro.core.risp import RISP

    m = dsv2.Dims.from_hf(run.config)
    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[run.config["torch_dtype"]]
    cfg = dsv2.program_config(m, run.config["name"], dtype)
    serve = run.config["serve"]
    chunk, max_len = serve["chunk"], serve["max_len"]
    workdir = run.fresh_workdir()

    params = dsv2.program_params(run.seed, m, dtype)
    jax.block_until_ready(params)
    run.mark("weights")
    client = Client(str(workdir / "store"))
    engine = client.serve_engine(cfg, params, max_len=max_len, chunk=chunk)
    if fault is not None:
        fault(engine)
    # one request no timed request shares compiles every program the window runs
    warm = traffic.rng_for(run.seed, 99).integers(1, m.vocab, chunk + 1).astype(np.int32)
    engine.generate(warm, max_new_tokens=2)
    run.mark("warm-up")
    calls: list = []
    tracer = None
    if run.trace:
        tracer = ScopeTracer(run, run.mix.get("trace_seconds", 8.0),
                             program_scopes(engine, chunk))
    record_calls(engine, calls)
    if run.trace:
        harness.wrap_steps(engine, PROGRAMS)
        run.mark("scopes")
    engine.policy = RISP()  # the window's history starts empty
    reqs = traffic.serve_requests(run.mix, run.seed, run.seconds, m.vocab)

    doc0 = client.metrics_doc()
    served = []  # (request, stats, chunk, latency, tokens, done, begun, calls)
    failed = 0
    t0 = time.perf_counter()
    setup_s = t0 - run.t_start
    traced: list = []
    with CompileCounter() as compiles:
        if tracer:
            tracer.start(t0)
        for q in reqs:
            now = time.perf_counter()
            due = t0 + q.arrival_s
            if due > now:
                time.sleep(due - now)
            begun = time.perf_counter()
            first = len(calls)
            try:
                with annotate("bench.request"):
                    out, st = engine.generate(q.prompt, max_new_tokens=q.max_new_tokens)
                done = time.perf_counter()
                served.append((q, st, chunk, done - due, out, done, begun, calls[first:]))
            except Exception as e:  # noqa: BLE001 - a failed request is a miss
                failed += 1
                served.append((q, None, chunk, float("inf"), None, time.perf_counter(),
                               begun, []))
                print(f"bench: request {q.index} failed: {e!r}", flush=True)
            if tracer and tracer.due(time.perf_counter()):
                traced = [s for s in served if s[1] is not None]
                tracer.stop()
        if tracer:
            if tracer.reduction is None:
                traced = [s for s in served if s[1] is not None]
            tracer.stop()
    t_end = max(s[5] for s in served)
    doc1 = client.metrics_doc()
    memory = harness.peak_bytes()

    ok = [s for s in served if s[1] is not None]
    lat = [s[3] for s in served]
    delta = {n: harness.series_total(doc1, n) - harness.series_total(doc0, n) for n in (
        "repro_serve_chunks_total", "repro_serve_chunks_skipped_total",
        "repro_serve_snapshot_save_seconds", "repro_serve_snapshot_load_seconds",
        "repro_serve_requests_total", "repro_serve_expert_assignments_total",
        "repro_serve_expert_held_assignments_total",
    )}
    e2e = {"setup_s": setup_s, "serve_latency_p90_s": harness.percentile(lat, 90)}
    peak = flops.peaks(jax.devices()[0].device_kind) if run.trace else None
    # each traced request's step calls, (kind, cache_len, tokens), with their counts
    per_req = []
    for q, st, c, *_, rec in traced:
        shape = step_calls([(q, st, c)])
        assert len(shape) == len(rec), (len(shape), len(rec))
        per_req.append((q, st, c, [(s, np.asarray(h)) for s, (_, h) in zip(shape, rec)]))
    trace_calls = [(s, h) for *_, cs in per_req for s, h in cs]
    scope_s = tracer.scope_s if tracer else {}
    layer = {
        "kind": "serve",
        "stats": [s[1] for s in ok],
        "counters": delta,
        "trace": tracer.reduction if tracer else None,
        "window_s": t_end - t0,
        "latencies": lat,
        "snapshots_saved": sum(s[1].stored_prefixes for s in ok),
        "peak": peak,
        "useful_flops_in_trace": useful_flops(m, per_req),
        "calls_in_trace": [(k, dsv2.step_work(m, n, c, h)) for (k, n, c), h in trace_calls],
        "scope_s": scope_s,
        # the least work of each scope's traced calls, for its roofline
        "mla_prefill_work": [dsv2.attention_core(m, n, c, layers=m.n_layers)
                             for (k, n, c), _ in trace_calls if k == "prefill"],
        "expert_work": [dsv2.routed_work(m, h) for _, h in trace_calls],
    }
    n_new = sum(s[1].n_new_tokens for s in ok)
    print(
        f"bench: {len(served)} requests, {failed} failed, {n_new} tokens, "
        f"window {t_end - t0:.3f}s, setup {setup_s:.3f}s, "
        f"chunks skipped {delta['repro_serve_chunks_skipped_total']:.0f}/"
        f"{delta['repro_serve_chunks_total']:.0f}, expert assignments "
        f"{delta['repro_serve_expert_held_assignments_total']:.0f} held of "
        f"{delta['repro_serve_expert_assignments_total']:.0f}", flush=True)
    for q, st, _, latency, _, done, begun, _ in sorted(ok, key=lambda s: -s[3])[:3]:
        print(f"bench: slow request {q.index}: latency {latency:.3f}s, waited "
              f"{begun - t0 - q.arrival_s:.3f}s, served in {done - begun:.3f}s, "
              f"prefill {st.prefill_s:.3f}s, decode {st.decode_s:.3f}s", flush=True)
    if scope_s:
        for prog, by in sorted(scope_s.items()):
            total = sum(by.values())
            claimed = total - by.get("other", 0.0)
            print(f"bench: {prog} device {total:.4f}s, scopes claim "
                  f"{100 * claimed / max(total, 1e-12):.1f}%: "
                  + ", ".join(f"{k} {v:.4f}s" for k, v in sorted(by.items())), flush=True)
    print(f"bench: set-up {run.marks_line()}", flush=True)

    window_compiles = compiles.count
    attempted = len(served)
    sample = _sample([s[:7] for s in ok], run.seed, run.mix.get("check", {}), chunk)
    hits = [decode_hits(ok, p, out, chunk) for p, out in sample]
    client.close()
    del engine, params, client, reqs, served, ok, traced, calls, per_req
    gc.collect()
    checks = check_served(run, m, dtype, sample, hits)
    return Outcome(
        attempted=attempted, failed=failed, end_to_end=e2e, layer=layer,
        checks=checks, memory_peak_bytes=memory, window_compiles=window_compiles,
        trace=tracer.reduction if tracer else None,
    )


def decode_hits(ok, prompt, out, chunk) -> list[np.ndarray]:
    """The route counts [n_moe, held + 1] of each decode step of the served
    request whose padded prompt and output these are, in order: step ``j``
    routed the token that served token ``j + 1`` was read from."""
    for q, *_, got, _, _, rec in ok:
        if len(got) == len(out) and np.array_equal(got, out) and \
                np.array_equal(_lm.padded(q.prompt, chunk), prompt):
            return [np.asarray(h) for kind, h in rec if kind == "decode"]
    raise LookupError("a sampled request is not among those served")


def miss_share(gaps, gap: float) -> float:
    """Share of served tokens whose reference logit lies more than ``gap``
    below the reference's best."""
    return float((np.asarray(gaps) > gap).mean())


def served_checks(gaps: list[np.ndarray], check: dict) -> list[Check]:
    """The cell's checks over each sampled request's per-token gaps.

    ``served_miss_share``: the share of served tokens that the reference
    ranks more than ``served_miss_gap`` below its best.  The widest gap
    alone is no test here: a bfloat16 rounding that flips a near-tied
    routing choice moves one token's logits by up to ~0.6, as far as the
    float8 control's widest gap, while the control misses on a sixth or more
    of the tokens and bfloat16 on at most one or two (PERF.md §2).

    ``served_head_misses``: the requests that miss at one of their first two
    served tokens, the prefill chunk's last row and the first decode step
    over the cache prefill wrote.  A fault at that hand-off between the step
    programs misses in every request, but adds one token in ~40 to the
    share; a sound program misses there in no request or, rarely, one."""
    limit, head_limit = check["served_miss_share_limit"], check["served_head_misses_limit"]
    if not gaps:
        return [Check("served_miss_share", float("inf"), limit),
                Check("served_head_misses", float("inf"), head_limit)]
    gap = check["served_miss_gap"]
    head = sum(bool((g[:2] > gap).any()) for g in gaps)
    return [Check("served_miss_share", miss_share(np.concatenate(gaps), gap), limit),
            Check("served_head_misses", float(head), head_limit)]


def route_flips(held: np.ndarray, hits: list[np.ndarray]) -> list[list[int] | None]:
    """Per served token of one request: the MoE layers in which the held
    experts that the program's decode step chose differ from those that the
    reference (``held`` [n_moe, tokens, held]) chose; None for the first
    token, which a prefill chunk served among its other rows."""
    n_held = held.shape[-1]
    out: list = [None]
    for t in range(1, held.shape[1]):
        prog = hits[t - 1][:, :n_held] > 0
        out.append([int(i) for i in np.flatnonzero((prog != held[:, t]).any(-1))])
    return out


def print_route_flips(gaps, held, hits, gap: float, widest: int = 3) -> None:
    """How far the tokens that miss are those at which the program chose
    other held experts than the reference in some layer, and the widest
    gaps with the layers where they chose differently."""
    flips = [route_flips(hd, h) for hd, h in zip(held, hits)]
    tokens = [(float(g[t]), r, t, f[t]) for r, (g, f) in enumerate(zip(gaps, flips))
              for t in range(1, len(g))]
    past = [x for x in tokens if x[0] > gap]
    within = [x for x in tokens if x[0] <= gap]
    print(f"bench: decoded tokens past served_miss_gap {gap}: {len(past)}, "
          f"{sum(bool(x[3]) for x in past)} with other held experts than the reference's "
          f"in some layer; within it: {len(within)}, {sum(bool(x[3]) for x in within)} "
          f"with other held experts", flush=True)
    for g, r, t, f in sorted(tokens, key=lambda x: -x[0])[:widest]:
        print(f"bench: gap {g!r} at request {r} token {t}: held experts chosen "
              f"otherwise in MoE layers {f}", flush=True)


def check_served(run, m, dtype, sample, hits) -> list[Check]:
    """``served_checks`` of the sample against the reference; ``hits``: each
    sampled request's decode steps' route counts, for ``print_route_flips``."""
    import jax

    check = run.config["check"]
    if not sample:
        return served_checks([], check)
    seqs, rows = rows_of(sample)
    t = time.perf_counter()
    with jax.default_matmul_precision("highest"):
        ref, held = dsv2.reference_logits(run.seed, m, seqs, rows, dtype=dtype, routes=True)
    gaps = [served_gaps([r], [s]) for r, s in zip(ref, sample)]
    print(f"bench: checked {sum(len(g) for g in gaps)} served tokens of {len(sample)} "
          f"requests in {time.perf_counter() - t:.1f}s; widest gap "
          f"{max(float(g.max()) for g in gaps)!r}", flush=True)
    print_route_flips(gaps, held, hits, check["served_miss_gap"])
    return served_checks(gaps, check)
