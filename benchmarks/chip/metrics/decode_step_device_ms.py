"""Model step (models/model.py via the engine's jitted decode step):
mean device milliseconds per decode call, from the trace."""

from chipbench.readings import DECODE_PROGRAM, mean


def read(run):
    if run.trace is None:
        return None
    m = mean(run.trace["calls"].get(DECODE_PROGRAM, []))
    return None if m is None else 1000.0 * m
