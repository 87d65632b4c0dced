"""Serving driver: RISP-prefix-cache engine over a request stream.

    PYTHONPATH=src python -m repro.launch.serve --arch tinyllama-1.1b --smoke \
        --requests 8
"""
from __future__ import annotations

import argparse

import numpy as np

import jax

from repro.configs import get_config
from repro.configs.base import ShapeCell
from repro.models.layers import init_params
from repro.runtime import enable_compile_cache
from repro.serve import ServeEngine
from repro.train import build_param_specs


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama-1.1b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--max-len", type=int, default=256)
    ap.add_argument("--chunk", type=int, default=16)
    ap.add_argument("--system-len", type=int, default=48)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch, smoke=args.smoke)
    cell = ShapeCell("s", "train", {"seq_len": 16, "global_batch": 1})
    params = init_params(
        jax.random.PRNGKey(0), build_param_specs(cfg, cell), cfg.dtype
    )
    engine = ServeEngine(cfg, params, max_len=args.max_len, chunk=args.chunk)

    rng = np.random.default_rng(0)
    system = rng.integers(0, cfg.vocab, size=args.system_len).tolist()
    tot_prefill = tot_skipped = tot_chunks = 0
    for i in range(args.requests):
        user = rng.integers(0, cfg.vocab, size=12).tolist()
        _, st = engine.generate(system + user, max_new_tokens=args.max_new)
        tot_prefill += st.prefill_s
        tot_skipped += st.chunks_skipped
        tot_chunks += st.n_chunks
        print(f"req {i}: skipped {st.chunks_skipped}/{st.n_chunks} chunks, "
              f"prefill {st.prefill_s*1e3:.1f} ms, decode {st.decode_s*1e3:.1f} ms")
    print(f"total: prefill {tot_prefill:.2f}s, chunks skipped "
          f"{tot_skipped}/{tot_chunks}, snapshots {engine.n_snapshots}")


if __name__ == "__main__":
    main()
