"""Runs of the benchmark's cells at a tiny size on the CPU, for its tests.

The harness's look for a chip is skipped; everything else of a run is the
benchmark's own code: set-up, window, check and result."""
from __future__ import annotations

import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "bench"
if str(BENCH / "lib") not in sys.path:
    sys.path.insert(0, str(BENCH / "lib"))

import harness  # noqa: E402


def tiny_run(cell: str, seed: int, seconds: float, workdir: Path, *, trace: bool = False,
             dims: dict | None = None):
    bench = benchmark()
    spec = {w["name"]: w for w in bench["workloads"]}[cell]
    config = {c["name"]: c for c in bench["configs"]}[spec["config"]]["file"]
    mix = spec["traffic"]
    family = "serve" if cell.startswith("serve") else "wf"
    e2e = [m for m in bench["end_to_end"]
           if m["name"] == "setup_s" or m["name"].startswith(family)]
    run = harness.CellRun(
        name=cell, seed=seed, seconds=seconds, trace=trace,
        cell=spec,
        config=json.loads((ROOT / config).read_text()),
        mix=json.loads((BENCH / "traffic" / f"{mix}.json").read_text()),
        end_to_end=e2e,
        per_layer=[m for m in bench["per_layer"] if m["moves"] in {e["name"] for e in e2e}],
    )
    run.workdir = Path(workdir) / cell
    if run.config["driver"] == "serve_lm":
        run.config.update(
            hidden_size=64, intermediate_size=128, num_attention_heads=4,
            num_key_value_heads=4, num_hidden_layers=2, vocab_size=256,
            torch_dtype="float32",
        )
        run.config.update(dims or {})
        run.config["serve"] = {"max_len": 96, "chunk": 16}
        run.mix["system_prompts"]["tokens"] = 24
        run.mix["user_tokens"].update(min=2, max=16, median=6)
        run.mix["output_tokens"].update(min=2, max=8, median=4)
        run.mix["arrivals"]["rate_per_s"] = 10
        run.mix["check"] = {"served_tokens": 64, "max_requests": 8}
    else:
        run.config.update(images_per_dataset=32, image_height=16, image_width=16)
        run.mix["check"] = {"max_workflows": 6}
    return run


def drive(run, fault=None):
    driver = harness.load_module(BENCH / "drivers" / f"{run.config['driver']}.py")
    return driver.run(run, fault=fault)


def benchmark() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())
