"""Readings that the DeepSeek-V2 cell's limits are set from: the program's and
the control's, over many seeds, in one process.

    python3 bench/calibrate_dsv2.py --seconds 12 --seeds 1,2,3

For each seed it runs the cell's window (short, at the cell's own load) and
checks the sample as the cell does, which also prints where the program chose
other held experts than the reference.  Then the control, the float8 (e4m3)
reference put in the program's place, serves the token it ranks first at the
same positions, and the same checks read it.  Not one of the benchmark's
runs: it prints no result line, one ``reading`` line per seed.
"""
from __future__ import annotations

import argparse
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH / "lib"), str(BENCH.parent / "src")]

import dsv2  # noqa: E402
import harness  # noqa: E402
from harness import Check  # noqa: E402

CELL = "serve-dsv2-doc8k-poisson"


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args()
    for seed in (int(s) for s in args.seeds.split(",")):
        run = harness.CellRun.from_benchmark(
            BENCH.parent / "BENCHMARK.json", CELL, seed, args.seconds, False)
        run.require_chips()
        run.enable_compile_cache()
        driver = harness.load_module(BENCH / "drivers" / "serve_dsv2.py")
        reference = dsv2.reference_logits

        def check(run, m, dtype, sample, hits, _d=driver, _served=driver.check_served):
            import jax

            refs = []
            dsv2.reference_logits = lambda *a, **k: refs.append(reference(*a, **k)) or refs[-1]
            try:
                program = _served(run, m, dtype, sample, hits)
            finally:
                dsv2.reference_logits = reference
            seqs, rows = _d.rows_of(sample)
            with jax.default_matmul_precision("highest"):
                low = reference(run.seed, m, seqs, rows, dtype=dtype, quant=True)
            picks = [(p, lo.argmax(axis=-1)) for (p, _), lo in zip(sample, low)]
            gaps = [_d.served_gaps([r], [s]) for r, s in zip(refs[0][0], picks)]
            control = _d.served_checks(gaps, run.config["check"])
            return program + [Check(f"control.{c.name}", c.value, float("nan")) for c in control]

        driver.check_served = check
        out = driver.run(run)
        vals = " ".join(f"{c.name}={c.value!r}" for c in out.checks)
        print(f"reading seed={seed} attempted={out.attempted} failed={out.failed} {vals}",
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
