"""Dry-run machinery tests: sharding resolution + subprocess smoke compile."""
import json
import subprocess
import sys
from pathlib import Path

import pytest

from repro.configs import get_config
from repro.launch.analysis import parse_collectives, scan_correct


def test_resolve_spec_divisibility_fallbacks():
    from jax.sharding import PartitionSpec as P

    from repro.launch.sharding import DEFAULT_RULES, resolve_spec, rules_for

    class FakeMesh:
        shape = {"pod": 2, "data": 16, "model": 16}

    rules = dict(DEFAULT_RULES)
    # heads divisible -> model
    assert resolve_spec(("embed", "heads", "head_dim"), (4096, 32, 128), FakeMesh(), rules) == P(None, "model")
    # heads not divisible -> replicated (no within-head fallback)
    assert resolve_spec(("embed", "heads", "head_dim"), (2560, 8, 256), FakeMesh(), rules) == P()
    # qwen2: experts 60 fail, expert_ff takes model
    assert resolve_spec(("experts", "embed", "expert_ff"), (60, 2048, 1408), FakeMesh(), rules) == P(None, None, "model")
    # deepseek-v2 override: experts -> data, expert_ff -> model
    ds = get_config("deepseek-v2-236b")
    r2 = rules_for(ds)
    assert resolve_spec(("experts", "embed", "expert_ff"), (160, 5120, 1536), FakeMesh(), r2) == P("data", None, "model")
    # batch over (pod, data) jointly
    assert resolve_spec(("batch", "seq"), (256, 4096), FakeMesh(), rules) == P(("pod", "data"))
    # batch=1 cannot shard; kv_seq picks data
    assert resolve_spec(("batch", "kv_seq"), (1, 524288), FakeMesh(), rules) == P(None, "data")


def test_parse_collectives_counts_and_bytes():
    hlo = """
  %p0 = bf16[64,128]{1,0} parameter(0)
  %ag = bf16[64,2048]{1,0} all-gather(%p0), dimensions={1}
  %ar = f32[32,32]{1,0} all-reduce(%x), to_apply=%sum
  %rs = f32[4,32]{1,0} reduce-scatter(%ar), dimensions={0}
  %cp = bf16[8,8]{1,0} collective-permute(%p1)
"""
    stats = parse_collectives(hlo)
    assert stats.count_by_kind == {
        "all-gather": 1, "all-reduce": 1, "reduce-scatter": 1, "collective-permute": 1
    }
    assert stats.bytes_by_kind["all-reduce"] == 2 * 32 * 32 * 4
    # all-gather: result - operand
    assert stats.bytes_by_kind["all-gather"] == 64 * 2048 * 2 - 64 * 128 * 2
    assert stats.total_bytes > 0


def test_scan_correct_linearity():
    # fixed=10, body=5: q1=15, q2=20 -> L=30 gives 10+150
    assert scan_correct(15, 20, 30) == 10 + 30 * 5


@pytest.mark.slow
def test_dryrun_subprocess_smoke(tmp_path):
    """End-to-end dry-run CLI on the 8-device smoke mesh (subprocess: the
    forced device count must be set before jax initializes)."""
    repo = Path(__file__).resolve().parents[1]
    out = tmp_path / "dryrun"
    res = subprocess.run(
        [
            sys.executable, "-m", "repro.launch.dryrun",
            "--arch", "tinyllama-1.1b", "--shape", "train_4k",
            "--mesh", "pod", "--smoke-mesh", "--remat", "full",
            "--out", str(out),
        ],
        cwd=repo,
        env={
            "PYTHONPATH": str(repo / "src"),
            "PATH": "/usr/bin:/bin",
            "JAX_PLATFORMS": "cpu",
        },
        capture_output=True,
        text=True,
        timeout=900,
    )
    assert res.returncode == 0, res.stdout + res.stderr
    recs = [json.loads(p.read_text()) for p in out.glob("*.json")]
    assert len(recs) == 1 and recs[0]["status"] == "ok"
    r = recs[0]["roofline"]
    assert r["flops_per_chip"] > 0 and r["hbm_bytes_per_chip"] > 0
    assert recs[0]["memory"]["peak_hbm_bytes"] > 0
