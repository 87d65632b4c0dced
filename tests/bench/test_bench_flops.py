"""Operation and byte counts of the step programs at DeepSeek-7B widths,
against numbers worked out by hand, and the table of peaks."""
import json

import pytest

from benchtiny import BENCH

import flops  # noqa: E402
import lm  # noqa: E402

DIMS = lm.Dims.from_hf(json.loads((BENCH / "configs" / "deepseek-7b-l16.json").read_text()))


def test_dims_are_the_published_widths_at_16_layers():
    assert (DIMS.d_model, DIMS.n_heads, DIMS.n_kv_heads, DIMS.d_head, DIMS.d_ff, DIMS.vocab) == (
        4096, 32, 32, 128, 11008, 102400)
    assert DIMS.n_layers == 16


def test_first_prefill_chunk():
    # per layer, 256 tokens: q,k,v 2*256*4096*96*128 = 25_769_803_776;
    # scores and values 4*32*128*(256*257/2) = 538_968_064; output
    # 2*256*4096*4096 = 8_589_934_592; gate, up, down 6*256*4096*11008 =
    # 69_256_347_648.  Sixteen layers, and logits for the last row only
    # (2*4096*102400 = 838_860_800): 1.667 TFLOP.  Counting every row's logits
    # and the embedding as matrix work, as 2 * 256 * 4.08e9 does, gives 2.09.
    w = flops.prefill_work(DIMS, 0, 256)
    assert w.flops == 16 * 104_155_054_080 + 838_860_800
    assert w.flops == pytest.approx(1.667e12, rel=1e-3)


def test_decode_step_bytes():
    # weights read once: 16 layers of 202_383_360 plus the final norm and the
    # head (4096 + 4096*102400), in bfloat16; the live cache of 1536 slots
    # (the new one written) at 16_384 bytes a slot a layer; one embedding row,
    # one token id and 102400 float32 logits
    w = flops.decode_work(DIMS, 1535)
    weights = (16 * 202_383_360 + 4096 + 4096 * 102400) * 2
    assert w.bytes == weights + 16 * 16_384 * 1536 + 4096 * 2 + 4 + 102400 * 4
    assert w.bytes == 7_718_207_492
    peak = flops.peaks("TPU v5 lite")
    # bound by memory: 9.42 ms at 819 GB/s
    assert w.seconds(peak) == pytest.approx(7_718_207_492 / 819e9)
    assert w.flops / peak["flops"] < w.bytes / peak["bytes_per_s"]


def test_later_chunks_attend_further():
    a, b = flops.prefill_work(DIMS, 0, 256), flops.prefill_work(DIMS, 1280, 256)
    assert b.flops - a.flops == 16 * 4 * 32 * 128 * 256 * 1280
    assert b.bytes - a.bytes == 16 * 16_384 * 1280


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        flops.peaks("cpu")
    assert flops.peaks("TPU v5 lite")["flops"] == 197e12
