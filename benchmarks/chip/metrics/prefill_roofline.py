"""Model step: the prefill calls' least time on the chip (operations
over peak FLOP/s, the last position's logits only, or bytes over HBM
bandwidth, whichever is larger), over their device time, in percent."""

from chipbench.readings import PREFILL_PROGRAM, roofline_share


def read(run):
    return roofline_share(run, "prefill", PREFILL_PROGRAM)
