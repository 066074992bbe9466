"""Executor (core/executor.py, serve/batcher.py): the share of the
traced window in which the executor's thread was neither waiting in
assign nor inside ``colony.engine.generate``, so the chip was idle: its
RPCs and their signatures, building the batch, uploading results,
closing. Read on the host's clock (``program_spans.idle_shares``)."""

from chipbench.program_spans import idle_shares


def read(run):
    shares = idle_shares(run)
    return None if shares is None else shares["executor"]
