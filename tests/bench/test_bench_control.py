"""Each cell's control against its limit, at a size a test run holds: the
reference put in the program's place one precision lower must come out not
correct, while the program itself passes.  On the chip the same readings,
at the cells' own sizes, set the limits (``bench/calibrate.py``; PERF.md)."""

import jax.numpy as jnp
import pytest

from benchtiny import BENCH, tiny_run

import harness  # noqa: E402  (on the path through benchtiny)

# a model wide enough, with a vocabulary large enough, that float8 rounding
# moves the reference's choice among near-tied logits as it does at full size
SERVE_DIMS = dict(hidden_size=256, intermediate_size=512, num_attention_heads=4,
                  num_key_value_heads=4, num_hidden_layers=4, vocab_size=32768,
                  torch_dtype="bfloat16")


def _readings(run, driver, check_name, reading):
    got = {}

    def check(*args):
        got["program"], got["control"] = reading(*args)
        return []

    setattr(driver, check_name, check)
    driver.run(run)
    return got["program"], got["control"]


@pytest.mark.parametrize("seed", [4, 5])
def test_serve_control_fails_the_limit(seed, tmp_path):
    run = tiny_run("serve-sysprompt-poisson", seed, 1.0, tmp_path, dims=SERVE_DIMS)
    run.mix["output_tokens"].update(min=16, max=32, median=24)
    run.mix["check"] = {"served_tokens": 256, "max_requests": 16}
    driver = harness.load_module(BENCH / "drivers" / "serve_lm.py")
    prog, ctrl = _readings(
        run, driver, "check_served",
        lambda r, dims, sample, chunk, max_len: driver.control_gap(r, dims, sample, max_len),
    )
    limit = run.config["check"]["served_logit_gap_limit"]
    assert prog <= limit < ctrl


@pytest.mark.parametrize("seed", [6, 7])
def test_workflow_control_fails_the_limit(seed, tmp_path):
    run = tiny_run("wf-galaxy-reuse", seed, 1.0, tmp_path)
    driver = harness.load_module(BENCH / "drivers" / "workflow.py")
    prog, ctrl = _readings(
        run, driver, "check_results",
        lambda r, sample, shared: (
            max(driver.reference_errors(r, sample, shared)),
            max(driver.reference_errors(r, sample, shared, round_to=jnp.bfloat16)),
        ),
    )
    limit = run.config["check"]["result_rel_err_limit"]
    assert prog <= limit < ctrl
