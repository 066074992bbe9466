"""Model step (models/model.py via the engine's jitted prefill): mean
device milliseconds per prefill call, from the trace."""

from chipbench.readings import PREFILL_PROGRAM, mean


def read(run):
    if run.trace is None:
        return None
    m = mean(run.trace["calls"].get(PREFILL_PROGRAM, []))
    return None if m is None else 1000.0 * m
