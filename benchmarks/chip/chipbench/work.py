"""Operations and bytes that a served call needs, from its shapes.

They count the work the algorithm needs, whatever implements it: a
decode step reads the parameters and the keys and values of the
positions before it, not the empty slots of a cache that is longer; a
prefill computes the logits of the last position only. An implementation
that does less than the program does today reads as a higher share of
the roofline, never above 100%.
"""

from __future__ import annotations

from .weights import Dims

BYTES = 2  # bf16, the type the configurations serve in


def layer_matrix_params(d: Dims) -> int:
    attn = d.d_model * d.head_dim * (2 * d.heads + 2 * d.kv_heads)  # wq, wo, wk, wv
    return attn + 3 * d.d_model * d.d_ff  # w_gate, w_up, w_down


def param_count(d: Dims) -> int:
    norms = (2 if d.norm == "layernorm" else 1) * d.d_model
    per_layer = layer_matrix_params(d) + 2 * norms
    head = 0 if d.tied else d.d_model * d.vocab
    return d.layers * per_layer + d.vocab * d.d_model + head + norms


def _kv_bytes(d: Dims, batch: int, positions: int) -> int:
    return 2 * d.layers * batch * positions * d.kv_heads * d.head_dim * BYTES


def prefill_flops(d: Dims, batch: int, seq: int) -> float:
    matmul = 2 * batch * seq * d.layers * layer_matrix_params(d)
    attn = d.layers * 4 * batch * d.heads * d.head_dim * seq * (seq + 1) / 2  # causal
    return matmul + attn + 2 * batch * d.d_model * d.vocab  # last position's logits


def prefill_bytes(d: Dims, batch: int, seq: int) -> float:
    return param_count(d) * BYTES + _kv_bytes(d, batch, seq) + batch * seq * d.d_model * BYTES


def decode_flops(d: Dims, batch: int, pos: int) -> float:
    matmul = 2 * batch * d.layers * layer_matrix_params(d)
    attn = d.layers * 4 * batch * d.heads * d.head_dim * (pos + 1)  # keys 0..pos
    return matmul + attn + 2 * batch * d.d_model * d.vocab


def decode_bytes(d: Dims, batch: int, pos: int) -> float:
    """Parameters, the K and V of positions before ``pos``, the new K and V."""
    return (param_count(d) * BYTES + _kv_bytes(d, batch, pos) + _kv_bytes(d, batch, 1)
            + batch * d.d_model * BYTES)


def generate_calls(batch: int, seq: int, new_tokens: int):
    """The (kind, batch, seq_or_pos) calls of one generate: one prefill,
    then a decode step for each token after the first."""
    yield "prefill", batch, seq
    for i in range(new_tokens - 1):
        yield "decode", batch, seq + i


def flops(d: Dims, kind: str, batch: int, n: int) -> float:
    return prefill_flops(d, batch, n) if kind == "prefill" else decode_flops(d, batch, n)


def nbytes(d: Dims, kind: str, batch: int, n: int) -> float:
    return prefill_bytes(d, batch, n) if kind == "prefill" else decode_bytes(d, batch, n)


def roofline_s(d: Dims, kind: str, batch: int, n: int, peaks: dict) -> float:
    """The least time the chip could take for one call."""
    return max(flops(d, kind, batch, n) / peaks["bf16_flops_per_s"],
               nbytes(d, kind, batch, n) / peaks["hbm_bytes_per_s"])
