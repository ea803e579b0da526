"""Qwen3's needed-work counts (``bench/models/qwen3.py``) against hand
counts at a small shape, and their independence from the kernels' block
sizes; the peak table."""

import pytest

from perfkit import peaks, spec
from repro.kernels import counting

qwen3 = spec.model_module({"model_type": "qwen3"})
S = qwen3.Shape(layers=2, d_model=8, heads=4, kv_heads=2, head_dim=4, d_ff=16,
               vocab=10, lora_rank=2, lora_targets=("q", "k", "v", "o"))


def test_token_flops_by_hand():
    # per layer: q 2*8*16 + k 2*8*8 + v 2*8*8 + o 2*16*8 = 256+128+128+256 = 768
    # LoRA: q 2*8*2+2*2*16=96, k 2*8*2+2*2*8=64, v 64, o 2*16*2+2*2*8=96 -> 320
    # MLP: 3*2*8*16 = 768; attention at position 5: 4*4*4*6 = 384
    per_layer = 768 + 320 + 768 + 384
    assert qwen3.token_flops(S, 5, emits=False) == 2 * per_layer
    assert qwen3.token_flops(S, 5, emits=True) == 2 * per_layer + 2 * 8 * 10


def test_span_flops_is_the_sum_of_its_tokens():
    for start, n, emits in ((0, 1, True), (7, 5, False), (30, 17, True)):
        want = sum(qwen3.token_flops(S, start + i, emits and i == n - 1)
                   for i in range(n))
        assert qwen3.span_flops(S, start, n, emits) == want
    assert qwen3.span_flops(S, 3, 0, True) == 0


def test_ragged_extend_need_by_hand():
    # row (start 2, 3 tokens): contexts 3+4+5 = 12 -> 4*H*D*12 = 768 flops;
    # bytes: q in and out 2*3*4*4*4 = 384, K and V of 5 positions 2*5*2*4*4 = 320
    f, b = qwen3.ragged_extend_need(S, [(2, 3), (9, 0)])
    assert (f, b) == (768, 384 + 320)


def test_fused_sgmv_need_by_hand():
    # q: 3 tokens, d_in 8, d_out 16, rank 2, 2 adapters
    f, b = qwen3.fused_sgmv_need(S, "q", 3, 2)
    assert f == 2 * 3 * 8 * 16 + 2 * 3 * (8 * 2 + 2 * 16)
    assert b == (8 * 16 + 3 * (8 + 16) + 2 * (8 * 2 + 2 * 16)) * 4
    assert qwen3.fused_sgmv_need(S, "q", 0, 2) == (0, 0)


@pytest.mark.parametrize("block", [8, 16, 32])
def test_needed_work_ignores_block_sizes(block):
    """The kernel's own replay changes with its blocks; the yardstick
    does not take them at all."""
    start, lens = [40, 0, 7], [24, 1, 0]
    replay = counting.ragged_extend_counts(3, S.heads, S.kv_heads, 32, 128,
                                           S.head_dim, start, lens,
                                           block_q=block, block_k=block)
    base = counting.ragged_extend_counts(3, S.heads, S.kv_heads, 32, 128,
                                         S.head_dim, start, lens,
                                         block_q=64, block_k=64)
    assert replay["hbm_bytes"] != base["hbm_bytes"] or replay["flops"] != base["flops"]
    f, b = qwen3.ragged_extend_need(S, zip(start, lens))
    # real work: contexts 41..64 of row 0 and 1 of row 1; row 2 is empty
    assert f == 4 * S.heads * S.head_dim * (sum(range(41, 65)) + 1)
    assert b == (2 * 25 * S.heads * S.head_dim
                 + 2 * (64 + 1) * S.kv_heads * S.head_dim) * 4


def test_peaks_table():
    p = peaks.peaks("TPU v5 lite")
    assert p["bf16_flops"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peaks("cpu")
    # memory bound: 819 bytes at 819 GB/s is a nanosecond
    assert peaks.least_time(1.0, 819.0, "TPU v5 lite") == pytest.approx(1e-9)
