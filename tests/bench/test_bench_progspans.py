"""Idle time named by the program's spans: synthetic timelines across threads,
and the committed trace, which has no program spans, read as before."""
import random
from collections import defaultdict
from pathlib import Path

import pytest

import benchtiny  # puts the benchmark's library on the path
import devtrace
import harness
import progspans
from progspans import ThreadSpan

FIXTURE = Path(__file__).with_name("fixtures") / "engine_tiny.xplane.pb"


def named(gaps, bench=(), prog=()):
    into = defaultdict(float)
    progspans.name_gaps(gaps, sorted(bench, key=lambda s: s.start),
                        sorted(prog, key=lambda s: s.start), into)
    return dict(into)


def test_program_spans_name_idle_time_before_the_benchmarks():
    bench = [devtrace.Span("bench.submit", 0, 100)]
    prog = [ThreadSpan("repro.store.put", 10, 60, (0, 1))]
    assert named([(0, 100)], bench, prog) == {"bench.submit": 50, "repro.store.put": 50}


def test_innermost_program_span_of_a_thread_names_it():
    prog = [ThreadSpan("repro.node", 0, 100, (0, 1)),
            ThreadSpan("repro.store.put", 20, 80, (0, 1)),
            ThreadSpan("repro.store.lock", 20, 30, (0, 1))]
    assert named([(0, 100)], prog=prog) == {
        "repro.node": 40, "repro.store.lock": 10, "repro.store.put": 50}


def test_wait_spans_are_passed_over_while_another_thread_works():
    prog = [ThreadSpan("repro.sched.run", 0, 100, (0, 1)),
            ThreadSpan("repro.sched.wait", 5, 95, (0, 1)),
            ThreadSpan("repro.store.write", 20, 40, (0, 2))]
    # only the wait is open outside the write: the wait names that time
    assert named([(10, 90)], prog=prog) == {"repro.sched.wait": 60, "repro.store.write": 20}


def test_working_threads_share_a_gap_equally():
    prog = [ThreadSpan("repro.store.lock", 0, 100, (0, 1)),
            ThreadSpan("repro.store.write", 0, 50, (0, 2)),
            ThreadSpan("repro.module.compute", 50, 100, (0, 3)),
            ThreadSpan("repro.sched.wait", 0, 100, (0, 4))]
    assert named([(0, 100)], prog=prog) == pytest.approx(
        {"repro.store.lock": 50, "repro.store.write": 25, "repro.module.compute": 25})


def test_a_gap_with_no_program_span_keeps_the_benchmarks_rule():
    bench = [devtrace.Span("bench.request", 0, 100), devtrace.Span("step.decode_step", 10, 20)]
    prog = [ThreadSpan("repro.serve.plan", 200, 300, (0, 1))]
    # the whole gap takes the name at its middle, as devtrace names it
    assert named([(5, 25), (100, 150)], bench, prog) == {"step.decode_step": 20, "no span": 50}


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_named_gaps_add_up_to_the_idle_time(seed):
    rng = random.Random(seed)
    bench = [devtrace.Span("bench.submit", a, a + rng.uniform(1, 30))
             for a in (rng.uniform(0, 1000) for _ in range(20))]
    prog = []
    for th in range(4):
        t = rng.uniform(0, 50)
        while t < 1000:
            d = rng.uniform(1, 40)
            name = rng.choice(["repro.node", "repro.sched.wait", "repro.store.put"])
            prog.append(ThreadSpan(name, t, t + d, (0, th)))
            if rng.random() < 0.5:  # a nested span
                prog.append(ThreadSpan("repro.store.lock", t + d / 4, t + d / 2, (0, th)))
            t += d + rng.uniform(0, 20)
    edges = sorted(rng.uniform(0, 1100) for _ in range(40))
    gaps = list(zip(edges[0::2], edges[1::2]))
    got = named(gaps, bench, prog)
    assert sum(got.values()) == pytest.approx(sum(e - s for s, e in gaps), rel=1e-9)


@pytest.fixture(scope="module")
def both():
    pd = devtrace.load(FIXTURE)
    return devtrace.reduce_trace(pd), progspans.reduce_trace(pd)


def test_the_committed_trace_reads_as_it_did(both):
    old, new = both
    for name, value in vars(old).items():
        assert getattr(new, name) == value, name
    assert new.program_spans == 0
    assert sorted(new.gap_s.items(), key=lambda kv: -kv[1]) == old.idle_gaps
    assert sum(new.gap_s.values()) == pytest.approx(old.window_s - old.busy_s, rel=1e-6)


class _Work:
    def seconds(self, peak):
        return 1e-3


READERS = sorted(p.stem for p in (benchtiny.BENCH / "metrics").glob("*.py"))


@pytest.mark.parametrize("name", READERS)
def test_every_reader_reads_the_same_on_the_committed_trace(both, name):
    reader = harness.load_module(benchtiny.BENCH / "metrics" / f"{name}.py")
    old, new = both
    for kind in ("serve", "workflow"):
        def layer(tr):
            return {"kind": kind, "trace": tr, "peak": {"flops": 197e12},
                    "useful_flops_in_trace": 1e12, "calls_in_trace": [("prefill", _Work())],
                    "workflows_in_trace": 3, "workflows": 3, "modules": 9, "skipped": 4,
                    "store_s": 0.2, "stats": [], "counters": {
                        "repro_serve_chunks_total": 0, "repro_serve_requests_total": 0}}
        assert reader.read(layer(new)) == reader.read(layer(old))
