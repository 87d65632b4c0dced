"""The DeepSeek-V2 cell at a tiny size on the CPU: a sound run is correct,
each planted fault turns ``correct`` false, the driver refuses a program
that lacks the mechanisms, and the work counts equal a hand count."""
import dataclasses
import json
import time

import jax.numpy as jnp
import numpy as np
import pytest

from benchtiny import BENCH, drive, tiny_run

import dsv2  # noqa: E402  (on the path through benchtiny)
import harness  # noqa: E402

from repro.models import transformer
from repro.serve.engine import ServeEngine

CELL = "serve-dsv2-doc8k-poisson"
TINY = {
    "num_hidden_layers": 3, "hidden_size": 64, "num_attention_heads": 4,
    "num_key_value_heads": 4, "q_lora_rank": 24, "kv_lora_rank": 16,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "intermediate_size": 128, "moe_intermediate_size": 32, "vocab_size": 256,
    "torch_dtype": "float32",
}


def _run(tmp_path, seed=2**32 + 7, trace=False):
    run = tiny_run(CELL, seed, 1.0, tmp_path, trace=trace)
    run.config.update(TINY)
    run.config["deployment"] = {**run.config["deployment"], "n_routed_experts": 32}
    run.config["rope_scaling"] = {**run.config["rope_scaling"],
                                  "original_max_position_embeddings": 16}
    run.config["serve"] = {"max_len": 64, "chunk": 8}
    run.mix["system_prompts"].update(tokens=24, count=2)
    run.mix["user_tokens"].update(min=2, max=16, median=6)
    run.mix["output_tokens"].update(min=2, max=8, median=4)
    run.mix["arrivals"]["rate_per_s"] = 10
    run.mix["check"] = {"served_tokens": 64, "max_requests": 8}
    return run


def test_sound_run_is_correct(tmp_path):
    out = drive(_run(tmp_path))
    assert out.failed == 0 and out.attempted == 10
    assert out.correct, [(c.name, c.value, c.limit) for c in out.checks]
    c = out.layer["counters"]
    # every token of every step is routed to 6 experts in each of 2 MoE layers
    tokens = sum(st.n_chunks * 8 + st.n_new_tokens for st in out.layer["stats"])
    assert c["repro_serve_expert_assignments_total"] == tokens * 6 * 2
    assert 0 < c["repro_serve_expert_held_assignments_total"] < tokens * 6 * 2


def _rebuild(engine, cfg):
    """The engine's step programs built again from another config."""
    fresh = ServeEngine(cfg, engine.params, max_len=engine.max_len, chunk=engine.chunk)
    engine._prefill, engine._decode = fresh._prefill, fresh._decode


def _scale_one(engine):
    moe = dataclasses.replace(engine.cfg.moe, routed_scale=1.0)
    _rebuild(engine, dataclasses.replace(engine.cfg, moe=moe))


def _plain_rope(engine):
    _rebuild(engine, dataclasses.replace(engine.cfg, rope_scaling=None))


def _skip_kv_norm(monkeypatch):
    orig = transformer.mla_project

    def project(x, p, **kw):
        q, _, k_rope = orig(x, p, **kw)
        raw = jnp.einsum("bsd,dr->bsr", x, p["w_dkv"])[..., : p["kv_norm"].shape[-1]]
        return q, raw, k_rope

    monkeypatch.setattr(transformer, "mla_project", project)


def _drop_by_capacity(monkeypatch):
    from repro.models import moe

    orig = transformer.moe_ffn_dropless

    class OneSlot(moe.MoEDims):
        def capacity(self, group_size):
            return 1  # an expert takes one token of a chunk, the rest are dropped

    def capacity(x, p, dims):
        _, hits = orig(x, p, dims)
        layer = {k: v for k, v in p.items() if k != "layer"}
        layer.update({k: moe.expert_weights(p, k) for k in moe.EXPERT_WEIGHTS})
        return moe.moe_ffn(x, layer, OneSlot(**dataclasses.asdict(dims))), hits

    monkeypatch.setattr(transformer, "moe_ffn_dropless", capacity)


def _wrong_first_decode_row(engine):
    """The first decode step of each request serves a wrong token (its
    logits moved one vocabulary entry along); every other step is sound."""
    first = [False]
    prefill, decode = engine._prefill, engine._decode

    def on_prefill(*a):
        first[0] = True
        return prefill(*a)

    def on_decode(*a):
        logits, *rest = decode(*a)
        if first[0]:
            first[0] = False
            logits = jnp.roll(logits, 1, axis=-1)
        return (logits, *rest)

    engine._prefill, engine._decode = on_prefill, on_decode


@pytest.mark.parametrize("fault, check", [
    pytest.param(f, c, id=f) for f, c in (
        ("routed_scale_1", "served_miss_share"), ("no_kv_a_layernorm", "served_miss_share"),
        ("plain_rope", "served_miss_share"), ("capacity_drop", "served_miss_share"),
        ("first_decode_row", "served_head_misses"))
])
def test_fault_is_caught(fault, check, tmp_path, monkeypatch):
    engine_fault = None
    if fault == "routed_scale_1":
        engine_fault = _scale_one
    elif fault == "plain_rope":
        engine_fault = _plain_rope
    elif fault == "first_decode_row":
        engine_fault = _wrong_first_decode_row
    elif fault == "no_kv_a_layernorm":
        _skip_kv_norm(monkeypatch)
    else:
        _drop_by_capacity(monkeypatch)
    out = drive(_run(tmp_path), fault=engine_fault)
    checks = {c.name: c for c in out.checks}
    assert not out.correct and not checks[check].ok, [(c.name, c.value, c.limit)
                                                      for c in out.checks]


def test_float8_control_fails_the_limit(tmp_path, monkeypatch):
    """The float8 reference in the program's place misses the reference's
    best token on more of the served tokens than the limit allows."""
    driver = harness.load_module(BENCH / "drivers" / "serve_dsv2.py")
    samples = []
    monkeypatch.setattr(driver, "check_served", lambda run, m, dtype, sample, hits: samples.append(
        (run, m, dtype, sample)) or [])
    driver.run(_run(tmp_path))
    run, m, dtype, sample = samples[0]
    seqs, rows = driver.rows_of(sample)
    ref = dsv2.reference_logits(run.seed, m, seqs, rows, dtype=dtype)
    low = dsv2.reference_logits(run.seed, m, seqs, rows, dtype=dtype, quant=True)
    picks = [(p, lo.argmax(axis=-1)) for (p, _), lo in zip(sample, low)]
    check = run.config["check"]
    assert driver.miss_share(driver.served_gaps(ref, sample), check["served_miss_gap"]) == 0
    ctrl = driver.miss_share(driver.served_gaps(ref, picks), check["served_miss_gap"])
    assert ctrl > check["served_miss_share_limit"]


def test_one_miss_per_request_passes_the_share_but_not_the_head_check():
    """At the cell's size (4 requests of 40-70 served tokens) a fault at one
    position of every request moves the miss share by under the limit; the
    head check fails it, and a sound reading with two misses passes both."""
    driver = harness.load_module(BENCH / "drivers" / "serve_dsv2.py")
    check = json.loads((BENCH / "configs" / "deepseek-v2-l8-e8.json").read_text())["check"]
    lengths = (70, 40, 55, 48)

    def gaps(misses):
        out = [np.zeros(n) for n in lengths]
        for r, t in misses:
            out[r][t] = 0.4
        return out

    fault = {c.name: c for c in driver.served_checks(gaps([(r, 1) for r in range(4)]), check)}
    assert fault["served_miss_share"].ok and not fault["served_head_misses"].ok
    sound = driver.served_checks(gaps([(0, 30), (2, 1)]), check)
    assert all(c.ok for c in sound), [(c.name, c.value) for c in sound]
    assert [c.value for c in driver.served_checks([], check)] == [np.inf, np.inf]


def test_route_flips_name_the_layers_that_chose_otherwise():
    driver = harness.load_module(BENCH / "drivers" / "serve_dsv2.py")
    held = np.zeros((2, 3, 4), bool)  # 2 MoE layers, 3 served tokens, 4 held experts
    held[0, 1, 2] = held[1, 2, 0] = True
    same = np.array([[0, 0, 1, 0, 5], [0, 0, 0, 0, 6]])  # the reference's choice at token 1
    other = np.array([[0, 0, 0, 1, 5], [0, 1, 0, 0, 5]])  # token 2: both layers differ
    assert driver.route_flips(held, [same, other, same]) == [None, [], [0, 1]]


def test_refuses_a_program_without_the_mechanisms(tmp_path, monkeypatch):
    """A program whose step programs give no route counts (as before this
    configuration) is refused at once, before any weight is drawn."""
    def old_step(params, cfg, tokens, cache, cache_len, *, unroll=1):
        raise AssertionError("not to be called")

    def no_draw(*a, **k):
        raise AssertionError("weights drawn")

    monkeypatch.setattr(transformer, "prefill_chunk", old_step)
    monkeypatch.setattr(dsv2, "program_params", no_draw)
    t = time.perf_counter()
    with pytest.raises(SystemExit) as e:
        drive(_run(tmp_path))
    assert e.value.code not in (0, None)
    assert time.perf_counter() - t < 5


def test_held_share_and_program_scopes(tmp_path):
    """``held_expert_share`` reads the run's counters, and every named scope
    is found in the step programs' HLO (the ragged dots by their kernels)."""
    out = drive(_run(tmp_path))
    held = harness.load_module(BENCH / "metrics" / "held_expert_share.py")
    assert 0 < held.read(out.layer) < 100
    assert out.layer["scope_s"] == {} and out.layer["trace"] is None
    driver = harness.load_module(BENCH / "drivers" / "serve_dsv2.py")
    m = dsv2.Dims.from_hf(_run(tmp_path).config)
    cfg = dsv2.program_config(m, "t", jnp.float32)
    engine = ServeEngine(cfg, dsv2.program_params(1, m, jnp.float32), max_len=64, chunk=8)
    scopes = driver.program_scopes(engine, 8)
    assert set(scopes) == {"jit_prefill_chunk", "jit_decode_step"}
    for by_op in scopes.values():
        assert set(by_op.values()) == set(driver.SCOPES)


M = dsv2.Dims(n_layers=2, first_k_dense=1, d_model=8, n_heads=2, q_lora=4, kv_lora=6,
              nope=3, rope=2, v_dim=5, d_ff=10, d_expert=7, n_routed=4, held_first=0,
              n_held=2, top_k=2, n_group=2, topk_group=1, n_shared=1, routed_scale=16.0,
              norm_topk=False, vocab=11, rms_eps=1e-6, rope_theta=1e4,
              yarn=(40.0, 32.0, 1.0, 4096, 0.707, 0.707))


def _forms(n, c, h=2, r=6):
    """(decompressed, absorbed) operations of M's attention, by hand."""
    pairs = c * n + c * (c + 1) // 2
    decompressed = 2 * (n + c) * r * h * (3 + 5) + 2 * pairs * h * (3 + 2) + 2 * pairs * h * 5
    absorbed = (2 * c * h * 3 * r + 2 * pairs * h * (r + 2) + 2 * pairs * h * r
                + 2 * c * h * r * 5)
    return decompressed, absorbed


def test_attention_count_takes_the_lesser_form():
    w = dsv2.attention_core(M, 4, 3)
    assert _forms(4, 3) == (2064, 1584) and w.flops == 1584
    # a long chunk from an empty cache: decompressing its keys is cheaper
    dec, ab = _forms(0, 256)
    assert dsv2.attention_core(M, 0, 256).flops == dec < ab
    # one query against a long cache: absorbing is cheaper
    dec, ab = _forms(1000, 1)
    assert dsv2.attention_core(M, 1000, 1).flops == ab < dec
    assert w.bytes == (7 * 8 + 6 * 2 * 8 + 3 * 2 * 5 + 3 * 2 * 5) * 2
    assert dsv2.attention_core(M, 4, 3, layers=2).flops == 2 * w.flops


def test_step_work_equals_a_hand_count():
    hits = np.array([[3, 0, 5]])  # one MoE layer: 3 to held expert 0, 5 held elsewhere
    w = dsv2.step_work(M, 4, 3, hits, logit_rows=1)
    d, h = 8, 2
    proj = 2 * 3 * (d * 4 + 4 * h * 5 + d * (6 + 2) + h * 5 * d)
    attn = dsv2.attention_core(M, 4, 3, layers=2)
    flops = (2 * proj + attn.flops + 1 * 2 * 3 * 3 * d * 10 + 1 * 2 * 3 * (3 * d * 7 + d * 4)
             + 2 * 1 * d * 11 + 2 * 3 * d * 7 * 3)
    assert w.flops == flops
    attn_w = d * 4 + 4 + 4 * h * 5 + d * 8 + 6 + h * 5 * d + 2 * d
    weights = (2 * attn_w + 3 * d * 10 + (3 * d * 7 + d * 4) + d + d * 11) * 2
    routed = 3 * d * 7 * 2 * 1 + 3 * d * 2 * 2  # one expert's weights, 3 rows in and out
    assert w.bytes == weights + 2 * 7 * 8 * 2 + (3 * d * 2 + 3 * 4 + 11 * 4) + routed


def test_no_assignments_read_only_shared_and_attention():
    none = dsv2.step_work(M, 4, 3, np.zeros((1, 3), int))
    some = dsv2.step_work(M, 4, 3, np.array([[1, 1, 0]]))
    assert dsv2.routed_work(M, np.zeros((1, 3), int)).flops == 0
    assert dsv2.routed_work(M, np.zeros((1, 3), int)).bytes == 0
    assert some.flops - none.flops == 2 * 2 * 3 * 8 * 7
    assert some.bytes - none.bytes == 2 * 3 * 8 * 7 * 2 + 2 * 8 * 2 * 2
