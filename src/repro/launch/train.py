"""Production training driver: --arch config + mesh + fault-tolerant loop.

On a real pod this runs per-host under jax.distributed; here it drives the
same code on the local device (use --smoke for CI-scale configs).

    PYTHONPATH=src python -m repro.launch.train --arch tinyllama-1.1b --smoke \
        --steps 20 --batch 4 --seq 64
"""
from __future__ import annotations

import argparse
import tempfile

import numpy as np

import jax

from repro.configs import get_config
from repro.configs.base import ShapeCell
from repro.ckpt import CheckpointManager
from repro.models.layers import init_params, param_count
from repro.optim import AdamWConfig
from repro.runtime import TrainDriver, enable_compile_cache
from repro.train import build_param_specs, build_train_step, make_train_state


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama-1.1b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=10)
    ap.add_argument("--remat", default="none")
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--fail-at", type=int, default=None, help="inject failure")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch, smoke=args.smoke)
    if cfg.family != "lm":
        raise SystemExit("train.py drives LM archs; see examples/ for others")
    cell = ShapeCell("train", "train", {"seq_len": args.seq, "global_batch": args.batch})
    specs = build_param_specs(cfg, cell)
    print(f"arch={cfg.name} params={param_count(specs)/1e6:.1f}M")

    params = init_params(jax.random.PRNGKey(0), specs, cfg.dtype)
    state = make_train_state(params)
    step_fn = build_train_step(
        cfg,
        cell,
        AdamWConfig(warmup_steps=10, total_steps=args.steps),
        remat=args.remat,
        grad_accum=args.grad_accum,
    )

    def make_batch(step: int) -> dict:
        r = np.random.default_rng(step)
        toks = r.integers(0, cfg.vocab, size=(args.batch, args.seq + 1))
        return {
            "tokens": jax.numpy.asarray(toks[:, :-1], jax.numpy.int32),
            "targets": jax.numpy.asarray(toks[:, 1:], jax.numpy.int32),
        }

    ckpt_dir = args.ckpt_dir or tempfile.mkdtemp()
    driver = TrainDriver(
        train_step=step_fn,
        make_batch=make_batch,
        ckpt=CheckpointManager(ckpt_dir, keep=3, async_save=True),
        ckpt_every=args.ckpt_every,
        fail_at_steps=(args.fail_at,) if args.fail_at else (),
    )
    state, log = driver.run(state, args.steps)
    losses = [e["loss"] for e in log if "loss" in e]
    print(f"done: {args.steps} steps, loss {losses[0]:.3f} -> {losses[-1]:.3f}, "
          f"ckpts in {ckpt_dir}")


if __name__ == "__main__":
    main()
