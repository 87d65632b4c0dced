"""Idle time named by the program's own spans.

With ``repro.obs.tracing``'s profiler sink on
(``configure_tracing(None, profiler=True)``), every program span is a
``repro.<name>`` annotation on its thread's line of a ``/host:`` plane, on
the clock of the device's ``XLA Ops``.  This reduction names the device's
idle time by what the program was doing, before the benchmark's spans:

- a piece of idle time is shared equally among the threads that have a
  working program span open, each share named by that thread's innermost
  ``repro.*`` span; a span whose name ends in ``.wait`` is not working;
- where only waiting spans are open, the innermost of them names the piece;
- where no program span is open, the innermost benchmark span at the gap's
  middle names it, as ``devtrace.reduce_trace`` does.

A trace with no program spans reads exactly what ``devtrace`` reads.
"""
from __future__ import annotations

import bisect
from collections import defaultdict
from dataclasses import dataclass, field

import devtrace as dt
from devtrace import reduce_trace as devtrace_reduce

PROGRAM_PREFIX = "repro."
WAIT_SUFFIX = ".wait"


@dataclass
class ThreadSpan:
    name: str
    start: float  # ns
    end: float  # ns
    thread: tuple[int, int]  # (plane, line) of the host plane: one thread


@dataclass
class Reduction(dt.Reduction):
    """``devtrace.Reduction``, its idle gaps named by program spans first."""

    gap_s: dict[str, float] = field(default_factory=dict)  # every name, not a top
    program_spans: int = 0  # program spans in the trace


def program_spans(pd) -> list[ThreadSpan]:
    """Every ``repro.*`` annotation on a host plane, with its thread."""
    spans = []
    for p, plane in enumerate(pd.planes):
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith(PROGRAM_PREFIX):
                    spans.append(ThreadSpan(ev.name, ev.start_ns,
                                            ev.start_ns + ev.duration_ns, (p, i)))
    spans.sort(key=lambda s: s.start)
    return spans


def program_segments(spans: list[ThreadSpan]) -> tuple[list[float], list[dict | None]]:
    """The timeline cut where any program span begins or ends; each piece
    with the names sharing it (name -> share of the piece), ``None`` where
    no program span is open."""
    marks = sorted(
        [(sp.start, 1, i) for i, sp in enumerate(spans)]
        + [(sp.end, 0, i) for i, sp in enumerate(spans)]
    )
    open_: dict[tuple[int, int], set[int]] = defaultdict(set)
    inner: dict[tuple[int, int], ThreadSpan] = {}  # thread -> innermost open span
    starts: list[float] = []
    shares: list[dict | None] = []
    for t, opening, i in marks:
        th = spans[i].thread
        (open_[th].add if opening else open_[th].discard)(i)
        if open_[th]:
            inner[th] = min((spans[j] for j in open_[th]), key=lambda sp: sp.end - sp.start)
        else:
            inner.pop(th, None)
        working = [sp.name for sp in inner.values() if not sp.name.endswith(WAIT_SUFFIX)]
        if working:
            share: dict | None = defaultdict(float)
            for name in working:
                share[name] += 1.0 / len(working)
            share = dict(share)
        elif inner:
            share = {min(inner.values(), key=lambda sp: sp.end - sp.start).name: 1.0}
        else:
            share = None
        if starts and starts[-1] == t:
            shares[-1] = share
        else:
            starts.append(t)
            shares.append(share)
    return starts, shares


def name_gaps(gaps, bench_spans: list[dt.Span], prog: list[ThreadSpan],
              into: dict[str, float]) -> None:
    """Add each idle gap ``(start, end)``, in ns, to ``into`` under its names."""
    seg_starts, seg_names = dt.innermost_segments(bench_spans)
    p_starts, p_shares = program_segments(prog)

    def bench_name(t: float) -> str:
        i = bisect.bisect_right(seg_starts, t) - 1
        return seg_names[i] if i >= 0 else "no span"

    for s, e in gaps:
        if e <= s:
            continue
        j = max(bisect.bisect_right(p_starts, s) - 1, 0)
        pieces = []
        while j < len(p_starts) and p_starts[j] < e:
            lo = max(s, p_starts[j])
            hi = min(e, p_starts[j + 1]) if j + 1 < len(p_starts) else e
            if hi > lo:
                pieces.append((lo, hi, p_shares[j]))
            j += 1
        if not any(share for _, _, share in pieces):
            into[bench_name((s + e) / 2)] += e - s  # devtrace's own rule
            continue
        if pieces[0][0] > s:  # before the first program span's segment
            pieces.insert(0, (s, pieces[0][0], None))
        for lo, hi, share in pieces:
            if share is None:
                into[bench_name((lo + hi) / 2)] += hi - lo
            else:
                for name, frac in share.items():
                    into[name] += frac * (hi - lo)


def reduce_trace(pd, *, top: int = 10) -> Reduction:
    """``devtrace.reduce_trace``, with the idle gaps named by program spans
    first, and the whole map of idle time by name in ``gap_s``."""
    base = devtrace_reduce(pd, top=top)
    spans = dt.host_spans(pd)
    windows = [s for s in spans if s.name == dt.WINDOW_SPAN]
    planes = dt.device_planes(pd)
    if windows:
        lo, hi = windows[0].start, windows[0].end
    else:  # the whole trace
        evs = [e for p in planes for e in dt._line(p, "XLA Ops")]
        lo = min(e.start_ns for e in evs)
        hi = max(e.start_ns + e.duration_ns for e in evs)
    inner = [s for s in spans if s.name != dt.WINDOW_SPAN]
    prog = program_spans(pd)
    gap_ns: dict[str, float] = defaultdict(float)
    for plane in planes:
        ops = dt._line(plane, "XLA Ops")
        busy = dt._union(
            dt._clip(((e.start_ns, e.start_ns + e.duration_ns) for e in ops), lo, hi)
        )
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        name_gaps(zip(edges[0::2], edges[1::2]), inner, prog, gap_ns)
    n = len(planes)
    gap_s = {k: v / n / 1e9 for k, v in gap_ns.items()}
    ranked = sorted(gap_s.items(), key=lambda kv: -kv[1])
    return Reduction(**{**vars(base), "idle_gaps": ranked[:top]}, gap_s=gap_s,
                     program_spans=len(prog))
