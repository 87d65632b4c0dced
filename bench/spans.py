"""Run a cell's traced window with the program's own spans on the profiler's
clock, and print where the device's idle time went, by program span.

    python3 bench/spans.py --workload <cell> --seed <n> --seconds <s> --sink <0|1>

The run is the cell's ``--trace 1`` run with two changes: ``repro``'s
profiler sink is on for the traced part of the window (``--sink 0`` leaves
it off, to measure what it costs), and the trace's idle time is named by
program spans first (``progspans``).  It prints one ``spans`` line: the
end-to-end readings, the cell's per-layer readings, the readings of the
program's new spans and counters, the whole map of idle time by name, and
the program spans recorded.  Not one of the benchmark's runs: it prints no
result line.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH / "lib"), str(BENCH.parent / "src")]

import devtrace  # noqa: E402
import harness  # noqa: E402
import progspans  # noqa: E402

# the program's histograms that the workflow readings sum, per workflow
WORKFLOW_SERIES = {
    "store_lock_wait_ms_per_workflow": "repro_store_lock_wait_seconds",
    "sched_queue_ms_per_workflow": "repro_sched_queue_seconds",
    "module_host_ms_per_workflow": "repro_module_compute_seconds",
}


def with_sink(sink: bool) -> type:
    """The harness's tracer, with the program's profiler sink on while it traces."""
    from repro.obs.tracing import configure_tracing

    class SinkTracer(harness.Tracer):
        def start(self, now: float) -> None:
            super().start(now)
            configure_tracing(None, profiler=sink)

        def stop(self) -> None:
            configure_tracing(None)
            super().stop()

    return SinkTracer


def program_readings(layer: dict, docs: list[dict]) -> dict[str, float]:
    """The readings of the program's spans and counters, where the run has them."""
    out: dict[str, float] = {}
    tr = layer.get("trace")
    if layer.get("kind") == "workflow" and layer["workflows"] and len(docs) >= 2:
        for name, series in WORKFLOW_SERIES.items():
            spent = harness.series_total(docs[-1], series) - harness.series_total(docs[0], series)
            out[name] = 1e3 * spent / layer["workflows"]
        if tr is not None:
            store = sum(s for k, s in tr.gap_s.items() if k.startswith("repro.store."))
            out["store_idle_share.workflow"] = 100.0 * store / tr.window_s
    if layer.get("kind") == "serve":
        ttft = [st.first_token_s for st in layer["stats"]]
        if ttft:
            out["engine_ttft_ms_p90"] = 1e3 * harness.percentile(ttft, 90)
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--sink", type=int, choices=(0, 1), default=1)
    args = ap.parse_args()
    run = harness.CellRun.from_benchmark(
        BENCH.parent / "BENCHMARK.json", args.workload, args.seed, args.seconds, True)
    run.t_start = T_START
    run.require_chips()
    run.enable_compile_cache()

    from repro.api import Client

    docs: list[dict] = []
    metrics_doc = Client.metrics_doc

    def recorded(self):
        docs.append(metrics_doc(self))
        return docs[-1]

    Client.metrics_doc = recorded
    harness.Tracer = with_sink(bool(args.sink))
    # the tracer reduces through devtrace; progspans holds devtrace's own
    devtrace.reduce_trace = progspans.reduce_trace
    driver = harness.load_module(BENCH / "drivers" / f"{run.config['driver']}.py")
    out = driver.run(run)

    per_layer = {}
    for m in run.per_layer:
        value = harness.load_module(BENCH / "metrics" / f"{m['name']}.py").read(out.layer)
        if value is not None:
            per_layer[m["name"]] = value
    tr = out.trace
    print("spans " + json.dumps({
        "workload": run.name, "seed": run.seed, "sink": args.sink,
        "correct": out.correct, "attempted": out.attempted, "failed": out.failed,
        "end_to_end": out.end_to_end,
        "per_layer": per_layer,
        "program": program_readings(out.layer, docs),
        "busy_s": tr.busy_s, "window_s": tr.window_s,
        "program_spans": tr.program_spans,
        "program_spans_per_s": tr.program_spans / tr.window_s,
        "gap_s": dict(sorted(tr.gap_s.items(), key=lambda kv: -kv[1])),
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
