"""What every cell's run shares: finding its parts by name, the chip check,
the compile cache, counting compilations, tracing, and the result line."""
from __future__ import annotations

import importlib.util
import json
import os
import shutil
import sys
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any

BENCH = Path(__file__).resolve().parents[1]


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def load_module(path: Path):
    """Import a file by path (metric files carry dots in their names)."""
    if not path.is_file():
        raise FileNotFoundError(f"no file {path}")
    spec = importlib.util.spec_from_file_location(f"bench_{path.stem.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Check:
    """One number compared with the reference, beside its limit."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value <= self.limit  # NaN fails


@dataclass
class Outcome:
    """What a driver hands back after its window and its check."""

    attempted: int
    failed: int
    end_to_end: dict[str, float]
    layer: dict[str, Any]  # what the per-layer readers read
    checks: list[Check]
    memory_peak_bytes: int | None
    window_compiles: int
    trace: Any = None  # trace.Reduction of the traced window, with --trace 1

    def __post_init__(self) -> None:
        # an answer that never came is a wrong one
        self.checks = [Check("failed", float(self.failed), 0.0), *self.checks]

    @property
    def correct(self) -> bool:
        return len(self.checks) > 1 and all(c.ok for c in self.checks)


@dataclass
class CellRun:
    name: str
    seed: int
    seconds: float
    trace: bool
    cell: dict
    config: dict  # the configuration file's contents
    mix: dict  # the traffic file's contents
    end_to_end: list[dict]  # BENCHMARK.json entries this cell reports
    per_layer: list[dict]
    workdir: Path = field(default=None)  # scratch inside the checkout
    t_start: float = field(default_factory=time.perf_counter)  # the process's start
    marks: dict[str, float] = field(default_factory=dict)  # set-up phase -> its end

    def mark(self, phase: str) -> None:
        """Note when a phase of set-up ended, in seconds from the start."""
        self.marks[phase] = time.perf_counter() - self.t_start

    def marks_line(self) -> str:
        return ", ".join(f"{k} done at {v:.3f}s" for k, v in self.marks.items())

    @classmethod
    def from_benchmark(cls, path: Path, workload: str, seed: int, seconds: float,
                       trace: bool) -> "CellRun":
        bench = json.loads(Path(path).read_text())
        cells = {w["name"]: w for w in bench["workloads"]}
        if workload not in cells:
            raise KeyError(f"no workload {workload!r} in {path.name}")
        cell = cells[workload]
        conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
        config = json.loads((path.parent / conf["file"]).read_text())
        mix = json.loads((BENCH / "traffic" / f"{cell['traffic']}.json").read_text())

        def mine(entries):
            return [e for e in entries if workload in e.get("workloads", [workload])]

        return cls(
            name=workload, seed=seed, seconds=seconds, trace=trace, cell=cell,
            config=config, mix=mix, end_to_end=mine(bench["end_to_end"]),
            per_layer=mine(bench["per_layer"]),
            workdir=BENCH / ".work" / workload,
        )

    def require_chips(self) -> None:
        import jax

        try:
            devices = jax.devices()
        except RuntimeError as e:
            raise NoChip(f"JAX found no device ({e})") from e
        if devices[0].platform != "tpu":
            raise NoChip(f"JAX found no TPU (platform {devices[0].platform})")
        if len(devices) < self.cell["chips"]:
            raise NoChip(f"{len(devices)} chips, the cell asks for {self.cell['chips']}")

    def enable_compile_cache(self) -> str:
        """JAX's persistent compile cache: ``JAX_COMPILATION_CACHE_DIR`` where
        set, else a fixed directory in the checkout (the path is part of the
        cache's key).  Every program is kept, however fast it compiled."""
        import jax

        path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(BENCH / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        return path

    def fresh_workdir(self) -> Path:
        shutil.rmtree(self.workdir, ignore_errors=True)
        self.workdir.mkdir(parents=True)
        return self.workdir


class CompileCounter:
    """Counts the programs JAX traces or compiles while open."""

    EVENTS = (
        "/jax/core/compile/jaxpr_trace_duration",
        "/jax/core/compile/backend_compile_duration",
    )

    def __init__(self) -> None:
        self.count = 0
        self.seconds = 0.0

    def _on(self, event: str, duration: float, **_: Any) -> None:
        if event in self.EVENTS:
            self.count += 1
            self.seconds += duration

    def __enter__(self) -> "CompileCounter":
        import jax

        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def __exit__(self, *exc: Any) -> None:
        import jax

        jax.monitoring.unregister_event_duration_listener(self._on)


def annotate(name: str):
    import jax

    return jax.profiler.TraceAnnotation(name)


class Tracer:
    """A profiler trace over the start of the window, in a run of its own.

    ``start`` opens it with the window; ``due`` says when the traced part has
    run long enough; ``stop`` closes it and reduces it.  Python-level tracing
    stays off: it slows the host and the reduction reads only annotations."""

    def __init__(self, run: CellRun, seconds: float) -> None:
        self.dir = run.workdir / "trace"
        self.seconds = seconds
        self.t0 = None
        self.reduction = None
        self._window = None

    def start(self, now: float) -> None:
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(self.dir), profiler_options=opts)
        self._window = annotate("bench.window")
        self._window.__enter__()
        self.t0 = now

    def due(self, now: float) -> bool:
        return self._window is not None and now - self.t0 >= self.seconds

    def stop(self) -> None:
        import jax

        import devtrace as tr

        if self._window is None:
            return
        self._window.__exit__(None, None, None)
        self._window = None
        jax.profiler.stop_trace()
        files = sorted(self.dir.rglob("*.xplane.pb"))
        self.reduction = tr.reduce_trace(tr.load(files[-1]))
        shutil.rmtree(self.dir, ignore_errors=True)


def wrap_steps(engine_like, names: dict[str, str]) -> None:
    """Wrap each named step callable in a ``step.<program>`` annotation."""
    for attr, span in names.items():
        fn = getattr(engine_like, attr)

        def wrapped(*a, _fn=fn, _span=span):
            with annotate(_span):
                return _fn(*a)

        setattr(engine_like, attr, wrapped)


def peak_bytes() -> int | None:
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in jax.local_devices()]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def tree_bytes(path: Path) -> int:
    """Bytes in the files under ``path``: what a run's store wrote and kept."""
    return sum(f.stat().st_size for f in Path(path).rglob("*") if f.is_file())


def series_total(doc: dict, name: str) -> float:
    """Sum of a counter's values, or of a histogram's observations."""
    fam = doc.get(name)
    if not fam:
        return 0.0
    total = 0.0
    for s in fam["series"]:
        total += s["hist"]["sum"] if "hist" in s else s.get("value", 0.0)
    return total


def percentile(values: list[float], q: float) -> float:
    import numpy as np

    return float(np.percentile(np.asarray(values, np.float64), q))


def finish(run: CellRun, outcome: Outcome) -> int:
    """Print the compile count, the per-check lines and the result line."""
    import jax

    print(f"bench: {outcome.window_compiles} compilations inside the measured window",
          file=sys.stderr, flush=True)
    io = Path("/proc/self/io")
    if io.exists():
        wrote = dict(line.split(": ") for line in io.read_text().splitlines()).get("write_bytes")
        print(f"bench: this process wrote {wrote} bytes to disk", file=sys.stderr, flush=True)
    metrics: dict[str, dict] = {}
    if run.trace:
        for m in run.per_layer:
            reader = load_module(BENCH / "metrics" / f"{m['name']}.py")
            value = reader.read(outcome.layer)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in run.end_to_end:
            metrics[m["name"]] = {"value": outcome.end_to_end[m["name"]], "unit": m["unit"]}
    devices = jax.devices()
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": outcome.memory_peak_bytes,
    }
    result: dict[str, Any] = {
        "correct": outcome.correct,
        "attempted": outcome.attempted,
        "failed": outcome.failed,
        "metrics": metrics,
        "device": device,
    }
    if run.trace and outcome.trace is not None:
        device["busy_s"] = outcome.trace.busy_s
        device["window_s"] = outcome.trace.window_s
        result["breakdown"] = {
            "device_ops": [list(x) for x in outcome.trace.device_ops],
            "idle_gaps": [list(x) for x in outcome.trace.idle_gaps],
        }
    result["checks"] = {c.name: {"value": c.value, "limit": c.limit} for c in outcome.checks}
    for c in outcome.checks:
        print(f"check {c.name} {c.value!r} limit {c.limit!r} {'ok' if c.ok else 'FAILED'}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0
