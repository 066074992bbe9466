"""Generator: the share of the traced window in which the executor's
thread waited in the broker's assign (``colony.broker.assign_wait``), so
the chip was idle: no batch had been fired to it. Read on the host's
clock (``program_spans.idle_shares``)."""

from chipbench.program_spans import idle_shares


def read(run):
    shares = idle_shares(run)
    return None if shares is None else shares["no_batch"]
