"""Readings that a cell's limit is set from: the program's number and the
control's, over many seeds, in one process.

    python3 bench/calibrate.py --workload <cell> --seconds <s> --seeds 1,2,3

For each seed it runs the cell's window (short, at the cell's own load) and
then compares the same sample twice: the timed path against the reference,
and the control against the reference.  The control is the reference put in
the program's place one precision lower: float8 (e4m3) matrix products for
a model served in bfloat16; module inputs rounded to bfloat16 for workflows
computed in float32.  Not one of the benchmark's runs: it prints no result
line, one ``reading`` line per seed.
"""
from __future__ import annotations

import argparse
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH / "lib"), str(BENCH.parent / "src")]

import harness  # noqa: E402
from harness import Check  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args()
    for seed in (int(s) for s in args.seeds.split(",")):
        run = harness.CellRun.from_benchmark(
            BENCH.parent / "BENCHMARK.json", args.workload, seed, args.seconds, False)
        run.require_chips()
        run.enable_compile_cache()
        driver = harness.load_module(BENCH / "drivers" / f"{run.config['driver']}.py")
        if run.config["driver"] == "serve_lm":
            def check(run, dims, sample, chunk, max_len, _d=driver):
                prog, ctrl = _d.control_gap(run, dims, sample, max_len)
                return [Check("served_logit_gap", prog, float("nan")),
                        Check("control", ctrl, float("nan"))]

            driver.check_served = check
        else:
            def check(run, sample, shared, _d=driver):
                import jax.numpy as jnp

                prog = max(_d.reference_errors(run, sample, shared))
                ctrl = max(_d.reference_errors(run, sample, shared, round_to=jnp.bfloat16))
                return [Check("result_rel_err", prog, float("nan")),
                        Check("control", ctrl, float("nan"))]

            driver.check_results = check
        t = time.perf_counter()
        out = driver.run(run)
        vals = {c.name: c.value for c in out.checks}
        print(f"reading workload={args.workload} seed={seed} attempted={out.attempted} "
              f"failed={out.failed} program={vals[list(vals)[1]]!r} control={vals['control']!r} "
              f"seconds={time.perf_counter() - t:.1f}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
