"""Run one benchmark cell once and print its result as the last line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything a cell is made of is found by name from ``BENCHMARK.json``:
its configuration (``bench/configs/<config>.json``, whose ``driver`` key
names the code in ``bench/drivers/`` that serves it), its traffic mix
(``bench/traffic/<traffic>.json``) and, with ``--trace 1``, one reader per
per-layer metric (``bench/metrics/<metric>.py``).  Adding a cell, a mix, a
configuration or a metric adds files and entries and edits none.

With ``--trace 0`` the result carries the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics and the device's busy time from a
profiler trace.  Every run checks what the timed path produced against the
plain reference and prints each number compared beside its limit.  Where JAX
finds no TPU, or fewer chips than the cell asks for, it exits non-zero and
prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path[:0] = [str(BENCH / "lib"), str(ROOT / "src")]

from harness import CellRun, NoChip, finish, load_module  # noqa: E402


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    try:
        run = CellRun.from_benchmark(
            ROOT / "BENCHMARK.json", args.workload, args.seed, args.seconds, bool(args.trace)
        )
    except (FileNotFoundError, KeyError, ValueError) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    run.t_start = T_START
    try:
        run.require_chips()
    except NoChip as e:
        print(f"bench: {e}; nothing run", file=sys.stderr)
        return 3
    run.mark("chips")
    run.enable_compile_cache()
    driver = load_module(BENCH / "drivers" / f"{run.config['driver']}.py")
    outcome = driver.run(run)
    return finish(run, outcome)


if __name__ == "__main__":
    sys.exit(main())
