"""Observability overhead guard: metrics + tracing must stay off the hot path.

The observability PR moved every ad-hoc counter in the fabric onto the
unified ``repro.obs`` registry and threaded optional tracing through the
wire protocol.  This benchmark is the regression fence for that migration:

**Instrument cost** — ``counter.inc()`` on a pre-bound child (the pattern
every hot path uses), a labeled ``labels(...).inc()`` lookup, and
``histogram.observe()``, each in microseconds per call.

**Disabled-tracing cost** — ``span()`` with tracing off must return the
shared no-op span in well under a microsecond (asserted), because every
store get/put and every RPC now calls it unconditionally.

**Hot-path overhead** — the fabric's hottest operation is a local cache-hit
blob read (digest-verified, no network).  The instrumentation a single hit
executes (one pre-bound counter inc + one disabled span) must cost **<5%**
of the hit itself (asserted) — i.e. observability rides along, it never
taxes reuse.

**Enabled-tracing cost** — per-span cost with NDJSON recording on, and with
the profiler sink alone (a ``jax.profiler`` annotation per span), and a
``render_prometheus`` scrape of a fabric-sized registry, reported for
context (not asserted: recording is opt-in).

``--smoke`` (CI): same assertions, smaller rep counts.
"""
from __future__ import annotations

import os
import sys
import tempfile
import time

from repro.core import MemoryBackend
from repro.net import CachingBackend
from repro.obs.metrics import MetricsRegistry, render_prometheus
from repro.obs.tracing import NOOP_SPAN, configure_tracing, span


def _per_call(fn, reps: int) -> float:
    t0 = time.perf_counter()
    for _ in range(reps):
        fn()
    return (time.perf_counter() - t0) / reps


def _instrument_round(smoke: bool) -> tuple[list[str], float]:
    reps = 50_000 if smoke else 400_000
    reg = MetricsRegistry()
    plain = reg.counter("repro_bench_hits_total", "h")
    labeled = reg.counter("repro_bench_ops_total", "o", ("op",))
    bound = labeled.labels(op="get")  # the hot-path pattern: bind once
    hist = reg.histogram("repro_bench_wait_seconds", "w")

    inc_s = _per_call(plain.inc, reps)
    bound_s = _per_call(bound.inc, reps)
    lookup_s = _per_call(lambda: labeled.labels(op="get").inc(), reps)
    obs_s = _per_call(lambda: hist.observe(0.01), reps)
    lines = [
        f"obs_counter_inc,{inc_s * 1e6:.3f},pre-bound child",
        f"obs_counter_inc_bound,{bound_s * 1e6:.3f},labels() bound once",
        f"obs_counter_labeled_lookup,{lookup_s * 1e6:.3f},labels() per call",
        f"obs_histogram_observe,{obs_s * 1e6:.3f},fixed log buckets",
    ]
    return lines, bound_s


def _span_round(smoke: bool) -> tuple[list[str], float]:
    reps = 50_000 if smoke else 200_000
    configure_tracing(None)  # make sure recording is off

    def disabled():
        with span("x", kind="bench"):
            pass

    disabled_s = _per_call(disabled, reps)
    assert span("x") is NOOP_SPAN
    # near-zero: every store op calls this unconditionally now
    assert disabled_s < 1e-6, f"disabled span() costs {disabled_s * 1e9:.0f}ns/call"

    with tempfile.TemporaryDirectory(prefix="bench-obs-") as d:
        configure_tracing(d, "bench")

        def enabled():
            with span("x", kind="bench", op="get"):
                pass

        enabled_s = _per_call(enabled, reps // 10)
        configure_tracing(None)
        n_lines = sum(
            1 for f in os.listdir(d) for _ in open(os.path.join(d, f))
        )
        assert n_lines == reps // 10, "every enabled span must be recorded"
    # the profiler sink alone: a profiler annotation per span, no file, no ids
    configure_tracing(None, profiler=True)
    profiler_s = _per_call(enabled, reps // 10)
    configure_tracing(None)
    lines = [
        f"obs_span_disabled,{disabled_s * 1e6:.4f},noop fast path (asserted <1us)",
        f"obs_span_enabled,{enabled_s * 1e6:.3f},NDJSON recording on",
        f"obs_span_profiler,{profiler_s * 1e6:.3f},profiler annotation, no profiler session",
    ]
    return lines, disabled_s


def _hot_path_round(smoke: bool, bound_inc_s: float, noop_span_s: float) -> list[str]:
    reps = 2_000 if smoke else 10_000
    cache = CachingBackend(MemoryBackend(), capacity_bytes=8 << 20)
    blob = os.urandom(64 * 1024)
    cache.write_blob("k", "data", blob)
    assert cache.read_blob("k", "data") == blob  # warm: subsequent reads hit

    hit_s = _per_call(lambda: cache.read_blob("k", "data"), reps)
    per_hit_instr = bound_inc_s + noop_span_s
    overhead_pct = per_hit_instr / hit_s * 100.0
    assert overhead_pct < 5.0, (
        f"instrumentation is {overhead_pct:.2f}% of a cache-hit read "
        f"({per_hit_instr * 1e9:.0f}ns of {hit_s * 1e6:.1f}us)"
    )
    assert cache.hits >= reps  # deprecated alias still reads the registry
    return [
        f"obs_cache_hit_read,{hit_s * 1e6:.2f},"
        f"64KiB digest-verified hit; instrumentation {overhead_pct:.2f}% (asserted <5%)"
    ]


def _scrape_round(smoke: bool) -> list[str]:
    reg = MetricsRegistry()
    # a fabric-sized registry: ~20 families, a few labeled series each
    for i in range(20):
        fam = reg.counter(f"repro_bench_f{i}_total", f"family {i}", ("op",))
        for op in ("get", "put", "probe"):
            fam.labels(op=op).inc(i + 1)
    h = reg.histogram("repro_bench_lat_seconds", "lat", ("op",))
    for op in ("get", "put"):
        for v in (0.001, 0.01, 0.1):
            h.labels(op=op).observe(v)
    reps = 50 if smoke else 300
    scrape_s = _per_call(lambda: render_prometheus(reg.to_doc()), reps)
    text = render_prometheus(reg.to_doc())
    assert "# TYPE repro_bench_f0_total counter" in text
    return [f"obs_prometheus_scrape,{scrape_s * 1e6:.1f},20 families x 3 series"]


def run(smoke: bool = False) -> list[str]:
    instr_lines, bound_inc_s = _instrument_round(smoke)
    span_lines, noop_span_s = _span_round(smoke)
    hot_lines = _hot_path_round(smoke, bound_inc_s, noop_span_s)
    return instr_lines + span_lines + hot_lines + _scrape_round(smoke)


if __name__ == "__main__":
    print("\n".join(run(smoke="--smoke" in sys.argv)))
