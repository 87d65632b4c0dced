"""Smoke run of the system's main paths on one TPU chip, in one process.

    python chip_smoke.py

Phases, each through the entry points a user calls:

* serve: DeepSeek-7B at its published widths, depth cut from 30 to 16
  layers, seeded random bf16 weights, served through
  ``Client.serve_engine`` / ``ServeEngine.generate``: four requests that
  share one 1024-token system prompt.  Logits are checked against a
  ``transformer.forward`` over the same tokens, with no cache and no chunks.
* workflow: the thesis' segmentation pipeline through ``Client.run`` on 4096
  96x96 images, run until the PT policy's stored prefix is reused.
* kernels: the decode and flash attention Pallas kernels against their
  ``ref.py`` at DeepSeek-7B serving widths.

Any failed check raises and exits non-zero.  Where JAX finds no TPU it exits
non-zero before any phase and prints no result.  The last line of a run that
passes is one JSON object naming the device.  Every time printed is from a
smoke run, not a benchmark.
"""
from __future__ import annotations

import dataclasses
import json
import shutil
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent
sys.path[:0] = [str(REPO / "src"), str(REPO)]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from benchmarks.pipelines import PIPELINES, make_images, register_modules  # noqa: E402
from repro.api import Client, WorkflowSpec  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.kernels import (  # noqa: E402
    attention_ref,
    decode_attention,
    decode_attention_ref,
    flash_attention,
)
from repro.models import transformer  # noqa: E402
from repro.models.layers import init_params  # noqa: E402
from repro.runtime import enable_compile_cache  # noqa: E402

SERVE_LAYERS = 16  # of DeepSeek-7B's 30: the weights and a 2048-slot cache fit 16 GB
STORE_DIR = REPO / ".smoke_store"
LABEL = "smoke run, not a benchmark"

# Served logits against the uncached forward.  Both compute in bf16 (f32
# accumulation and softmax) in different orders: chunked prefill over the
# cache against one full-sequence pass.  They differ by bf16 roundings
# (unit roundoff 2**-8 = 3.9e-3) carried through every layer: a CPU run of
# this check at 16 layers and d_model 1024 gave a relative RMS error of 1.1e-2
# and a max error of 1.4e-2 of the largest logit, against 1.2e-6 in f32.
# Both bounds leave 2-3x of room; computing in a format with fp8's 2**-4
# roundoff would put the error near 0.2 and fail both.
LOGIT_REL_RMS_TOL = 3e-2
LOGIT_MAX_TOL = 5e-2
# Kernels against ref.py, bf16 inputs: the tolerance test_kernels.py uses.
KERNEL_TOL = 2e-2


class SmokeFailure(RuntimeError):
    """A phase produced a wrong or missing result."""


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling while open."""

    EVENTS = (
        "/jax/core/compile/jaxpr_trace_duration",
        "/jax/core/compile/jaxpr_to_mlir_module_duration",
        "/jax/core/compile/backend_compile_duration",
    )

    def __init__(self) -> None:
        self.seconds = 0.0

    def _on_event(self, event: str, duration: float, **_: object) -> None:
        if event in self.EVENTS:
            self.seconds += duration

    def __enter__(self) -> "CompileClock":
        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        return self

    def __exit__(self, *exc: object) -> None:
        jax.monitoring.unregister_event_duration_listener(self._on_event)


def peak_bytes() -> str:
    stats = jax.devices()[0].memory_stats()
    if not stats or "peak_bytes_in_use" not in stats:
        return "not reported"
    return str(stats["peak_bytes_in_use"])


class LogitTap:
    """Keeps the logits an engine's step programs return, in call order."""

    def __init__(self, engine) -> None:
        self.logits: list[jax.Array] = []
        engine._prefill = self._tap(engine._prefill)
        engine._decode = self._tap(engine._decode)

    def _tap(self, step):
        def tapped(*args):
            out = step(*args)
            self.logits.append(out[0])
            return out

        return tapped


def _logit_errors(served: jax.Array, ref: jax.Array) -> tuple[float, float]:
    s, r = np.asarray(served, np.float64), np.asarray(ref, np.float64)
    rel_rms = float(np.linalg.norm(s - r) / np.linalg.norm(r))
    rel_max = float(np.abs(s - r).max() / np.abs(r).max())
    return rel_rms, rel_max


# -- phases ------------------------------------------------------------------
def serve_phase(
    cfg,
    store_dir: Path,
    *,
    system_len: int = 1024,
    user_len: int = 64,
    n_requests: int = 4,
    new_tokens: int = 32,
    max_len: int = 2048,
    chunk: int = 256,
    seed: int = 0,
) -> dict:
    """Serve requests sharing one system prompt; check logits and reuse."""
    with CompileClock() as clock:
        params = jax.jit(
            lambda key: init_params(key, transformer.lm_specs(cfg), cfg.dtype)
        )(jax.random.PRNGKey(seed))
        forward = jax.jit(lambda p, t: transformer.forward(p, cfg, t)[0][0, -3:])
        rng = np.random.default_rng(seed)
        system = rng.integers(0, cfg.vocab, system_len)
        rows = []
        with Client(str(store_dir)) as client:
            engine = client.serve_engine(cfg, params, max_len=max_len, chunk=chunk)
            tap = LogitTap(engine)
            for i in range(n_requests):
                prompt = np.concatenate([system, rng.integers(0, cfg.vocab, user_len)])
                tap.logits.clear()
                out, st = engine.generate(prompt, max_new_tokens=new_tokens)
                # the last prefill's logits, then the first two decode steps'
                first = len(tap.logits) - new_tokens - 1
                served = tap.logits[first : first + 3]
                check(len(served) == 3, "need two decode steps to compare")
                check(
                    all(bool(jnp.isfinite(x).all()) for x in tap.logits),
                    f"request {i}: non-finite logits",
                )
                # the engine left-pads the prompt with token 0 to whole chunks
                padded = np.concatenate(
                    [np.zeros((-len(prompt)) % chunk, np.int64), prompt, out[:2]]
                )
                ref = forward(params, jnp.asarray(padded[None], jnp.int32))
                errs = [_logit_errors(s[0], r) for s, r in zip(served, ref)]
                rows.append((i, st, errs))
                print(
                    f"serve request {i}: prefill_s={st.prefill_s} "
                    f"decode_s={st.decode_s} chunks_skipped={st.chunks_skipped}/"
                    f"{st.n_chunks} logit_rel_rms={max(e[0] for e in errs)} "
                    f"logit_rel_max={max(e[1] for e in errs)} ({LABEL})",
                    flush=True,
                )
    for i, st, errs in rows:
        for pos, (rel_rms, rel_max) in zip(("prefill", "decode1", "decode2"), errs):
            check(
                rel_rms <= LOGIT_REL_RMS_TOL and rel_max <= LOGIT_MAX_TOL,
                f"request {i} {pos}: served logits differ from forward "
                f"(rel_rms {rel_rms}, rel_max {rel_max})",
            )
    check(rows[0][1].chunks_skipped == 0, "the first request found a snapshot")
    restored = [i for i, st, _ in rows if st.chunks_skipped > 0]
    check(bool(restored), "no request restored the shared prefix")
    print(
        f"serve: compile_s={clock.seconds} peak_bytes_in_use={peak_bytes()} "
        f"first_restored_request={restored[0]} ({LABEL})",
        flush=True,
    )
    return {"restored": restored, "compile_s": clock.seconds}


def workflow_phase(store_dir: Path, *, n_images: int = 4096, max_runs: int = 4) -> dict:
    """Rerun the segmentation pipeline until a run reuses a stored prefix."""
    with CompileClock() as clock:
        images = make_images(n=n_images)
        spec = WorkflowSpec.from_steps("images", PIPELINES["segmentation"])
        with Client(str(store_dir), policy="PT") as client:
            register_modules(client)
            runs = []
            while len(runs) < max_runs and not (runs and runs[-1].n_skipped):
                r = client.run(spec, images)
                jax.block_until_ready(r.output)
                runs.append(r)
                print(
                    f"workflow run {len(runs) - 1}: total_s={r.total_seconds} "
                    f"skipped={r.n_skipped}/{len(r.workflow)} "
                    f"stored={len(r.stored_keys)} ({LABEL})",
                    flush=True,
                )
    cold, warm = runs[0], runs[-1]
    check(cold.n_skipped == 0, "the cold run skipped modules")
    check(warm.n_skipped > 0, f"no run skipped a stored module in {max_runs} runs")
    cold_leaves = jax.tree_util.tree_leaves(cold.output)
    warm_leaves = jax.tree_util.tree_leaves(warm.output)
    check(
        jax.tree_util.tree_structure(cold.output)
        == jax.tree_util.tree_structure(warm.output)
        and all(
            a.dtype == b.dtype and np.array_equal(np.asarray(a), np.asarray(b))
            for a, b in zip(cold_leaves, warm_leaves)
        ),
        "the reused result differs from the cold run's",
    )
    device = jax.devices()[0]
    check(
        all(leaf.devices() == {device} for leaf in warm_leaves),
        f"the reused result does not live on {device}",
    )
    print(
        f"workflow: input_bytes={images.nbytes} runs={len(runs)} "
        f"compile_s={clock.seconds} peak_bytes_in_use={peak_bytes()} ({LABEL})",
        flush=True,
    )
    return {"runs": len(runs), "skipped": warm.n_skipped}


def kernel_phase(
    *,
    heads: int = 32,
    head_dim: int = 128,
    positions: int = 2048,
    batch: int = 4,
    interpret: bool = False,
    seed: int = 0,
) -> dict:
    """Run both attention kernels once against their references."""
    kq, kk, kv, kl = jax.random.split(jax.random.PRNGKey(seed), 4)
    bf16 = jnp.bfloat16
    kv_shape = (batch, positions, heads, head_dim)
    k = jax.random.normal(kk, kv_shape, bf16)
    v = jax.random.normal(kv, kv_shape, bf16)
    q = jax.random.normal(kq, (batch, heads, head_dim), bf16)
    lens = jax.random.randint(kl, (batch,), 1, positions + 1)
    out = decode_attention(q, k, v, lens, interpret=interpret)
    ref = decode_attention_ref(q[:, :, None], k, v, lens)[:, :, 0]
    dec_err = float(jnp.abs(out.astype(jnp.float32) - ref.astype(jnp.float32)).max())

    qf = jax.random.normal(kq, (1, positions, heads, head_dim), bf16)
    out = flash_attention(qf, k[:1], v[:1], causal=True, interpret=interpret)
    ref = attention_ref(qf, k[:1], v[:1], causal=True)
    fl_err = float(jnp.abs(out.astype(jnp.float32) - ref.astype(jnp.float32)).max())
    print(
        f"kernels: decode_attention max_abs_err={dec_err} "
        f"flash_attention max_abs_err={fl_err} (heads={heads} d={head_dim} "
        f"positions={positions})",
        flush=True,
    )
    check(dec_err <= KERNEL_TOL, f"decode_attention differs from ref by {dec_err}")
    check(fl_err <= KERNEL_TOL, f"flash_attention differs from ref by {fl_err}")
    return {"decode_err": dec_err, "flash_err": fl_err}


def main() -> int:
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(
            f"chip_smoke: JAX found no TPU (platform {dev.platform}); nothing run",
            file=sys.stderr,
        )
        return 1
    print(f"device: {dev.device_kind} count={len(devices)} jax={jax.__version__}")
    print(f"compile cache: {enable_compile_cache()}")
    base = get_config("deepseek-7b")
    cfg = dataclasses.replace(base, n_layers=SERVE_LAYERS)
    print(
        f"serve config: {base.name} n_layers cut {base.n_layers} -> {cfg.n_layers}; "
        f"d_model={cfg.d_model} heads={cfg.n_heads}/{cfg.n_kv_heads} "
        f"d_head={cfg.head_dim} d_ff={cfg.d_ff} vocab={cfg.vocab} as published",
        flush=True,
    )
    shutil.rmtree(STORE_DIR, ignore_errors=True)
    try:
        serve_phase(cfg, STORE_DIR / "serve")
        workflow_phase(STORE_DIR / "workflow")
        kernel_phase()
    finally:
        shutil.rmtree(STORE_DIR, ignore_errors=True)
    print(
        json.dumps(
            {
                "ok": True,
                "device": {
                    "platform": dev.platform,
                    "kind": dev.device_kind,
                    "count": len(devices),
                },
            }
        )
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
