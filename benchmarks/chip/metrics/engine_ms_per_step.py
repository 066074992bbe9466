"""Engine (serve/engine.py): milliseconds of engine.generate per model
call it made (one prefill and one decode step per token after the
first), host dispatch included."""


def read(run):
    steps = sum(c["new"] for c in run.calls)
    return 1000.0 * sum(c["end"] - c["start"] for c in run.calls) / steps if steps else None
