"""The trace reduction on a small trace recorded on a TPU v5e: two requests
of a two-layer engine, 3 prefill chunks and 3 decode steps each, with the
benchmark's spans."""
from pathlib import Path

import pytest

import benchtiny  # puts the benchmark's library on the path
import devtrace

FIXTURE = Path(__file__).with_name("fixtures") / "engine_tiny.xplane.pb"


@pytest.fixture(scope="module")
def reduction():
    return devtrace.reduce_trace(devtrace.load(FIXTURE))


def test_each_step_program_is_found_with_its_calls(reduction):
    assert reduction.step_calls == {"step.prefill_chunk": 6, "step.decode_step": 6}
    assert all(s > 0 for s in reduction.step_device_s.values())


def test_busy_time_lies_inside_the_window(reduction):
    assert reduction.n_devices == 1
    assert 0 < reduction.busy_s < reduction.window_s
    # a program's time runs from its first operation to its last, and holds
    # the short waits between them that busy time leaves out
    steps = sum(reduction.step_device_s.values())
    assert reduction.busy_s * 0.9 < steps < reduction.window_s
    assert 0 < reduction.idle_share < 1


def test_idle_gaps_are_named_by_the_spans_around_them(reduction):
    names = {n for n, _ in reduction.idle_gaps}
    assert names <= {"bench.request", "step.prefill_chunk", "step.decode_step", "no span"}
    assert "bench.request" in names
    gaps = sum(s for _, s in reduction.idle_gaps)
    assert gaps == pytest.approx(reduction.window_s - reduction.busy_s, rel=1e-6)


def test_top_operations_are_leaves_labelled_by_program(reduction):
    assert 0 < len(reduction.device_ops) <= 10
    for name, seconds in reduction.device_ops:
        prog, op = name.split(":", 1)
        assert not op.startswith(devtrace.CONTAINER_OPS)
        assert seconds > 0
    progs = {n.split(":")[0] for n, _ in reduction.device_ops}
    assert progs & {"step.prefill_chunk", "step.decode_step"}


def test_innermost_span_names_the_timeline():
    spans = [devtrace.Span("bench.request", 0, 100), devtrace.Span("step.decode_step", 10, 20)]
    starts, names = devtrace.innermost_segments(spans)
    assert list(zip(starts, names)) == [
        (0, "bench.request"), (10, "step.decode_step"), (20, "bench.request"), (100, "no span")]


def test_mfu_reads_the_traced_window(reduction):
    import layerreads

    peak = {"flops": 197e12}
    d = {"kind": "serve", "trace": reduction, "useful_flops_in_trace": 1e12, "peak": peak}
    assert layerreads.mfu(d) == pytest.approx(100.0 * 1e12 / (reduction.window_s * 197e12))
    assert layerreads.mfu({**d, "trace": None}) is None


METRICS = sorted(p.stem for p in (benchtiny.BENCH / "metrics").glob("*.py"))


@pytest.mark.parametrize("name", METRICS)
def test_a_reader_with_nothing_to_read_returns_nothing(name):
    import harness

    reader = harness.load_module(benchtiny.BENCH / "metrics" / f"{name}.py")
    assert reader.read({}) is None
    assert reader.read({"kind": "other", "trace": None}) is None
