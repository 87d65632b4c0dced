"""The engine's time to first token as its reader reads it, at a tiny size on
the CPU, and from an engine that does not time it."""
from types import SimpleNamespace

from benchtiny import BENCH, drive, tiny_run

import harness  # noqa: E402  (on the path through benchtiny)

READER = harness.load_module(BENCH / "metrics" / "engine_ttft_ms_p90.py")


def test_reads_the_window_requests(tmp_path):
    out = drive(tiny_run("serve-sysprompt-poisson", 3000000041, 1.0, tmp_path))
    ttft_ms = READER.read(out.layer)
    stats = out.layer["stats"]
    assert stats and 0 < ttft_ms <= 1e3 * max(out.layer["latencies"])
    assert ttft_ms <= 1e3 * max(st.prefill_s + st.decode_s + 0.05 for st in stats)


def test_an_engine_that_does_not_time_its_first_token_reads_nothing():
    old = SimpleNamespace(prefill_s=0.1, decode_s=0.1)
    assert READER.read({"kind": "serve", "stats": [old]}) is None
