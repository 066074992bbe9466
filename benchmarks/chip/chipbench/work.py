"""The calls a served generate makes, and the least time the chip could
take for one; the operations and bytes of a call are the block's own
(``flops`` and ``nbytes`` of ``blocks/<block>.py``)."""

from __future__ import annotations


def generate_calls(batch: int, seq: int, new_tokens: int):
    """The (kind, batch, seq_or_pos) calls of one generate: one prefill,
    then a decode step for each token after the first."""
    yield "prefill", batch, seq
    for i in range(new_tokens - 1):
        yield "decode", batch, seq + i


def roofline_s(block, dims, kind: str, batch: int, n: int, peaks: dict) -> float:
    """The least time the chip could take for one call of ``block``."""
    return max(block.flops(dims, kind, batch, n) / peaks["bf16_flops_per_s"],
               block.nbytes(dims, kind, batch, n) / peaks["hbm_bytes_per_s"])
