"""Whole step against the chip, as step_mfu, for the cells whose
latency it moves: it bounds what prefill_roofline can claim."""

from chipbench.readings import mfu


def read(run):
    return mfu(run)
