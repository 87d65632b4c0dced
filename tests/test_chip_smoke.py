"""chip_smoke.py on the CPU: its phases at smoke size, its refusal to run
without a TPU, and the compile-cache helper it shares with the launchers."""
import importlib.util
from pathlib import Path

import jax

from repro.configs import get_config
from repro.runtime import enable_compile_cache
from repro.runtime.platform import COMPILE_CACHE_DIR

REPO = Path(__file__).resolve().parents[1]


def _chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", REPO / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_serve_phase_smoke_config(tmp_path):
    res = _chip_smoke().serve_phase(
        get_config("deepseek-7b", smoke=True),
        tmp_path,
        system_len=96,
        user_len=8,
        new_tokens=4,
        max_len=256,
        chunk=32,
    )
    # request 1 sees the system prompt a second time and stores it (PT needs
    # support 2); requests 2 and 3 restore it
    assert res["restored"] == [2, 3]


def test_workflow_phase_smoke_size(tmp_path):
    res = _chip_smoke().workflow_phase(tmp_path, n_images=32)
    assert res == {"runs": 3, "skipped": 4}


def test_kernel_phase_interpret():
    res = _chip_smoke().kernel_phase(
        heads=4, positions=256, batch=2, interpret=True
    )
    assert res["decode_err"] <= 2e-2 and res["flash_err"] <= 2e-2


def test_main_refuses_without_tpu(capsys):
    assert jax.devices()[0].platform == "cpu"
    assert _chip_smoke().main() != 0
    out, err = capsys.readouterr()
    assert '"ok"' not in out
    assert "no TPU" in err


def test_compile_cache_honours_env(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before  # left to JAX


def test_compile_cache_fixed_path_in_checkout(monkeypatch):
    from jax.experimental.compilation_cache import compilation_cache as cc

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        assert enable_compile_cache() == str(REPO / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == str(COMPILE_CACHE_DIR)
        assert enable_compile_cache() == str(COMPILE_CACHE_DIR)  # stable
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
        cc.reset_cache()
