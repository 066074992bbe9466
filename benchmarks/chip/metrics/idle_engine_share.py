"""Engine (serve/engine.py): the share of the traced window in which the
chip was idle inside ``colony.engine.generate``: dispatch, compiles of
new shapes, the fetch of the tokens. Read on the host's clock, as the
span's time less the chip's busy time (``program_spans.idle_shares``)."""

from chipbench.program_spans import idle_shares


def read(run):
    shares = idle_shares(run)
    return None if shares is None else shares["engine"]
