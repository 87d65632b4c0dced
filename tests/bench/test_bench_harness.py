"""The harness end to end at a tiny size on the CPU: a sound run is correct,
and each fault the timed path can have turns ``correct`` false."""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from benchtiny import BENCH, ROOT, benchmark, drive, tiny_run

import harness  # noqa: E402  (on the path through benchtiny)


def test_every_part_is_found_by_name():
    b = benchmark()
    for cell in b["workloads"]:
        run = harness.CellRun.from_benchmark(ROOT / "BENCHMARK.json", cell["name"], 1, 1.0, True)
        assert (BENCH / "drivers" / f"{run.config['driver']}.py").is_file()
        assert run.end_to_end and any(m["name"] == "setup_s" for m in run.end_to_end)
        assert run.per_layer
        for m in run.per_layer:
            assert (BENCH / "metrics" / f"{m['name']}.py").is_file()
            assert m["moves"] in {e["name"] for e in run.end_to_end}


def test_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload", "wf-galaxy-reuse",
         "--seed", "3000000001", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120,
    )
    assert p.returncode != 0
    assert "{" not in p.stdout
    assert "no TPU" in p.stderr


def _serve_faults():
    def altered_token(engine):
        step = engine._decode

        def decode(*a):
            logits, cache, n = step(*a)
            return logits.at[:, 7].add(1e3), cache, n

        engine._decode = decode

    def state_unchanged(engine):
        step = engine._decode

        def decode(p, t, c, n):
            logits, _, n2 = step(p, t, c, n)
            return logits, c, n2

        engine._decode = decode

    return {"altered_token": altered_token, "state_unchanged": state_unchanged}


@pytest.mark.parametrize("seed", [3000000021, 2**33 + 21])
def test_serve_sound_run_is_correct(seed, tmp_path):
    out = drive(tiny_run("serve-sysprompt-poisson", seed, 1.5, tmp_path))
    assert out.correct, out.checks
    assert out.failed == 0 and out.attempted > 5
    assert out.window_compiles == 0


@pytest.mark.parametrize("fault", sorted(_serve_faults()))
def test_serve_fault_is_caught(fault, tmp_path):
    run = tiny_run("serve-sysprompt-poisson", 3000000022, 1.0, tmp_path)
    # long answers, so a decode step's lost state has later steps to mislead
    run.mix["output_tokens"].update(min=16, max=32, median=24)
    out = drive(run, fault=_serve_faults()[fault])
    assert not out.correct, out.checks


def _workflow_faults():
    def altered_answer(modules):
        inner = modules["analyze"]
        return {**modules, "analyze": lambda s, **kw: {
            k: (v * 1.001 if k == "spread" else v) for k, v in inner(s, **kw).items()}}

    def state_unchanged(modules):
        fit = modules["fit"]
        return {**modules, "fit": lambda x, n_clusters=8, iters=80: fit(x, n_clusters, 0)}

    def half_batch(modules):
        fit = modules["fit"]
        return {**modules, "fit": lambda x, n_clusters=8, iters=80: fit(
            x[: x.shape[0] // 2], n_clusters, iters)}

    return {"altered_answer": altered_answer, "state_unchanged": state_unchanged,
            "half_batch": half_batch}


@pytest.mark.parametrize("seed", [3000000023, 2**33 + 23])
def test_workflow_sound_run_is_correct(seed, tmp_path):
    out = drive(tiny_run("wf-galaxy-reuse", seed, 1.5, tmp_path))
    assert out.correct, out.checks
    assert out.failed == 0 and out.attempted > 5
    assert out.window_compiles == 0


@pytest.mark.parametrize("fault", sorted(_workflow_faults()))
def test_workflow_fault_is_caught(fault, tmp_path):
    run = tiny_run("wf-galaxy-reuse", 3000000024, 1.0, tmp_path)
    out = drive(run, fault=_workflow_faults()[fault])
    assert not out.correct, out.checks


def test_result_line_has_the_contract_keys(tmp_path, capsys):
    run = tiny_run("wf-galaxy-reuse", 3000000025, 0.5, tmp_path)
    out = drive(run)
    harness.finish(run, out)
    last = capsys.readouterr().out.strip().splitlines()[-1]
    res = json.loads(last)
    assert list(res)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert list(res)[-1] == "checks"
    assert {"wf_latency_p90_s", "wf_per_s", "setup_s"} <= set(res["metrics"])
