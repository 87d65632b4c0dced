"""Find an open-loop cell's knee: run its window at several fixed rates in one
process and print, for each, the latency tail and how far the queue drained
past the window.  The knee is the highest rate whose queue does not grow.

    python3 bench/sweep.py --workload <cell> --seed <n> --seconds <s> --rates 1.5,2,2.5

Not one of the benchmark's runs: it checks no output and prints no result line.
"""
from __future__ import annotations

import argparse
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH / "lib"), str(BENCH.parent / "src")]

import harness  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args()
    for rate in (float(r) for r in args.rates.split(",")):
        run = harness.CellRun.from_benchmark(
            BENCH.parent / "BENCHMARK.json", args.workload, args.seed, args.seconds, False)
        run.require_chips()
        run.enable_compile_cache()
        run.mix["arrivals"]["rate_per_s"] = rate
        driver = harness.load_module(BENCH / "drivers" / f"{run.config['driver']}.py")
        driver.check_served = lambda *a, **k: []
        out = driver.run(run)
        lat = sorted(out.layer["latencies"])
        n = len(lat)
        busy = sum(st.prefill_s + st.decode_s for st in out.layer["stats"])
        print(
            f"sweep rate={rate} requests={n} p50_s={lat[n // 2]!r} "
            f"p90_s={harness.percentile(lat, 90)!r} drain_s={out.layer['window_s'] - args.seconds!r} "
            f"engine_busy_share={busy / out.layer['window_s']!r} "
            f"snapshots_saved={out.layer['snapshots_saved']}",
            flush=True,
        )
    return 0


if __name__ == "__main__":
    sys.exit(main())
