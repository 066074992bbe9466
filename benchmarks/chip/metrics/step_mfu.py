"""Whole step against the chip: the operations that the window's
prefill and decode calls need (the block's ``flops``, blocks/<block>.py), over
the traced window's seconds times the chip's peak bf16 rate, in percent.
This entry moves output_tokens_per_s."""

from chipbench.readings import mfu


def read(run):
    return mfu(run)
