"""Model step: the decode calls' least time on the chip (the larger of
operations over peak FLOP/s and bytes over HBM bandwidth, counting the
parameters and the live keys and values), over their device time, in
percent."""

from chipbench.readings import DECODE_PROGRAM, roofline_share


def read(run):
    return roofline_share(run, "decode", DECODE_PROGRAM)
