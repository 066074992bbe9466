"""Broker (core/server.py assign): mean seconds from a batch process's
submission to its assignment to the executor."""

from chipbench.readings import batches, mean


def read(run):
    return mean([b["started"] - b["submitted"] for b in batches(run)])
