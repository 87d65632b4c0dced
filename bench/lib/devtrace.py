"""From a JAX profiler trace to the device numbers the benchmark reports.

A TPU trace (``jax.profiler.ProfileData``) has one plane per device, named
``/device:TPU:<n>``, whose ``XLA Modules`` line holds one event per program
execution and whose ``XLA Ops`` line holds the operations inside them; and a
``/host:CPU`` plane whose lines hold the host's spans, among them every
``jax.profiler.TraceAnnotation``.  Both are on one clock, in nanoseconds.

The benchmark names its own host spans: ``bench.window`` around the traced
window, ``bench.*`` around requests and submissions, and ``step.<program>``
around each dispatch of a step program it wants timed apart.  The step
programs of one engine are all ``jit__lambda`` to the profiler, so each is
told apart by the step spans around whose dispatches it runs.
"""
from __future__ import annotations

import bisect
from collections import Counter, defaultdict
from dataclasses import dataclass, field
from typing import Iterable

WINDOW_SPAN = "bench.window"
STEP_PREFIX = "step."
SPAN_PREFIXES = ("bench.", "step.")
# operations that hold others (a scan's loop): their time is their children's
CONTAINER_OPS = ("while", "conditional", "call")


@dataclass
class Span:
    name: str
    start: float  # ns
    end: float  # ns


@dataclass
class Reduction:
    """What one traced window reads."""

    window_s: float
    busy_s: float  # union of device operations, averaged over devices
    n_devices: int
    step_device_s: dict[str, float] = field(default_factory=dict)
    step_calls: dict[str, int] = field(default_factory=dict)
    device_ops: list[tuple[str, float]] = field(default_factory=list)
    idle_gaps: list[tuple[str, float]] = field(default_factory=list)

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def _union(intervals: Iterable[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(intervals, lo, hi):
    for s, e in intervals:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            yield s, e


def _op_name(full: str) -> str:
    """``%fusion.40 = bf16[...] fusion(...)`` -> ``fusion.40``."""
    return full.split(" = ", 1)[0].lstrip("%")


def host_spans(pd) -> list[Span]:
    spans = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(SPAN_PREFIXES):
                    spans.append(Span(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns))
    spans.sort(key=lambda s: s.start)
    return spans


def device_planes(pd) -> list:
    return [p for p in pd.planes if p.name.startswith("/device:TPU:")]


def _line(plane, name: str) -> list:
    for line in plane.lines:
        if line.name == name:
            return list(line.events)
    return []


def innermost_segments(spans: list[Span]) -> tuple[list[float], list[str]]:
    """The timeline cut where any span begins or ends, each piece named by
    the shortest span open over it (``no span`` where none is)."""
    marks = sorted(
        [(sp.start, 1, i) for i, sp in enumerate(spans)]
        + [(sp.end, 0, i) for i, sp in enumerate(spans)]
    )
    open_: set[int] = set()
    starts: list[float] = []
    names: list[str] = []
    for t, opening, i in marks:
        (open_.add if opening else open_.discard)(i)
        name = (
            min((spans[j] for j in open_), key=lambda sp: sp.end - sp.start).name
            if open_ else "no span"
        )
        if starts and starts[-1] == t:
            names[-1] = name
        else:
            starts.append(t)
            names.append(name)
    return starts, names


def step_programs(spans: list[Span], modules: list, near_ns: float = 2e6) -> dict[str, str]:
    """Program name of each step kind.

    The host's and the device's timestamps can disagree by a fraction of a
    millisecond, so a dispatch is matched to no single execution: each step
    kind takes the program with the most device time among the executions
    that start within ``near_ns`` of its dispatches."""
    starts = [m.start_ns for m in modules]
    near: dict[str, Counter] = defaultdict(Counter)
    for sp in spans:
        if not sp.name.startswith(STEP_PREFIX):
            continue
        i = bisect.bisect_left(starts, sp.start - near_ns)
        j = bisect.bisect_right(starts, sp.end + near_ns)
        for m in modules[i:j]:
            near[sp.name][m.name] += m.duration_ns
    return {kind: c.most_common(1)[0][0] for kind, c in near.items()}


def reduce_trace(pd, *, top: int = 10) -> Reduction:
    """Busy time, per-step device time, top operations and idle gaps."""
    spans = host_spans(pd)
    windows = [s for s in spans if s.name == WINDOW_SPAN]
    planes = device_planes(pd)
    if not planes:
        raise ValueError("the trace holds no TPU device plane")
    if windows:
        lo, hi = windows[0].start, windows[0].end
    else:  # the whole trace
        evs = [e for p in planes for e in _line(p, "XLA Ops")]
        lo = min(e.start_ns for e in evs)
        hi = max(e.start_ns + e.duration_ns for e in evs)
    window_ns = hi - lo

    busy_ns = 0.0
    step_ns: dict[str, float] = defaultdict(float)
    step_calls: dict[str, int] = defaultdict(int)
    op_ns: dict[str, float] = defaultdict(float)
    gap_ns: dict[str, float] = defaultdict(float)
    inner = [s for s in spans if s.name != WINDOW_SPAN]
    for plane in planes:
        modules = sorted(_line(plane, "XLA Modules"), key=lambda e: e.start_ns)
        ops = _line(plane, "XLA Ops")
        busy = _union(
            _clip(((e.start_ns, e.start_ns + e.duration_ns) for e in ops), lo, hi)
        )
        busy_ns += sum(e - s for s, e in busy)

        names = step_programs(spans, modules)
        label = {prog: kind for kind, prog in names.items()}
        mod_iv = []
        for m in modules:
            s, e = m.start_ns, m.start_ns + m.duration_ns
            mod_iv.append((s, e, label.get(m.name, m.name.split("(")[0])))
            clipped = min(e, hi) - max(s, lo)
            if m.name in label and clipped > 0:
                step_ns[label[m.name]] += clipped
                step_calls[label[m.name]] += 1
        mod_starts = [iv[0] for iv in mod_iv]
        for op in ops:
            s, e = op.start_ns, op.start_ns + op.duration_ns
            clipped = min(e, hi) - max(s, lo)
            if clipped <= 0 or _op_name(op.name).startswith(CONTAINER_OPS):
                continue
            i = bisect.bisect_right(mod_starts, s) - 1
            prog = mod_iv[i][2] if i >= 0 and mod_iv[i][1] >= s else "?"
            op_ns[f"{prog}:{_op_name(op.name)}"] += clipped

        # idle gaps, each named by the innermost benchmark span around its middle
        seg_starts, seg_names = innermost_segments(inner)
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        for s, e in zip(edges[0::2], edges[1::2]):
            if e <= s:
                continue
            i = bisect.bisect_right(seg_starts, (s + e) / 2) - 1
            gap_ns[seg_names[i] if i >= 0 else "no span"] += e - s

    n = len(planes)
    ranked = lambda d: sorted(  # noqa: E731
        ((k, v / n / 1e9) for k, v in d.items()), key=lambda kv: -kv[1]
    )[:top]
    return Reduction(
        window_s=window_ns / 1e9,
        busy_s=busy_ns / n / 1e9,
        n_devices=n,
        step_device_s={k: v / n / 1e9 for k, v in step_ns.items()},
        step_calls={k: v // n for k, v in step_calls.items()},
        device_ops=ranked(op_ns),
        idle_gaps=ranked(gap_ns),
    )


def load(path: str):
    import jax

    return jax.profiler.ProfileData.from_file(str(path))
