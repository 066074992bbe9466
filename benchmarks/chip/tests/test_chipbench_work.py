"""Operation and byte counts of the chip benchmark, against a hand count."""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from chipbench import blocks, work  # noqa: E402
from chipbench.peaks import peaks_for  # noqa: E402

import pytest  # noqa: E402

dense = blocks.load("dense")
Dims = dense.Dims

# granite-3-8b smoke widths: d 64, 4 heads of 16 over 2 KV heads, d_ff 208.
D = Dims(layers=2, d_model=64, heads=4, kv_heads=2, head_dim=16, d_ff=208, vocab=259,
         tied=True, norm="rmsnorm", norm_eps=1e-5, rope_theta=1e4)


def test_parameters():
    per_layer = 64 * 64 + 64 * 32 * 2 + 64 * 64 + 3 * 64 * 208 + 2 * 64  # q, k+v, o, mlp, norms
    assert dense.param_count(D) == 2 * per_layer + 259 * 64 + 64


def test_decode_by_hand():
    # batch 3 at position 10: matrices, attention over keys 0..10, last logits
    matmul = 2 * 3 * 2 * (64 * 64 + 64 * 32 * 2 + 64 * 64 + 3 * 64 * 208)
    attn = 2 * 4 * 3 * 4 * 16 * 11
    assert dense.decode_flops(D, 3, 10) == matmul + attn + 2 * 3 * 64 * 259
    kv = 2 * 2 * 3 * 10 * 2 * 16 * 2  # K and V, 2 layers, 3 rows, 10 positions
    new = 2 * 2 * 3 * 1 * 2 * 16 * 2
    assert dense.decode_bytes(D, 3, 10) == dense.param_count(D) * 2 + kv + new + 3 * 64 * 2


def test_prefill_by_hand():
    matmul = 2 * 2 * 8 * 2 * (64 * 64 + 64 * 32 * 2 + 64 * 64 + 3 * 64 * 208)
    attn = 2 * 4 * 2 * 4 * 16 * (8 * 9 // 2)  # causal: 1 + 2 + ... + 8 keys
    assert dense.prefill_flops(D, 2, 8) == matmul + attn + 2 * 2 * 64 * 259


def test_generate_calls_and_roofline():
    calls = list(work.generate_calls(4, 256, 3))
    assert calls == [("prefill", 4, 256), ("decode", 4, 256), ("decode", 4, 257)]
    peaks = peaks_for("TPU v5 lite")
    t = work.roofline_s(dense, D, "decode", 4, 256, peaks)
    assert t == max(dense.decode_flops(D, 4, 256) / 197e12, dense.decode_bytes(D, 4, 256) / 819e9)


def test_unknown_chip_is_an_error():
    with pytest.raises(KeyError):
        peaks_for("TPU v9 imaginary")
