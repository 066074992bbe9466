"""Executor (runtime/jax_executor.py ServeExecutor, make_batch_handler):
mean seconds a batch process runs beyond its engine.generate call
(building the batch, uploading each result to CFS, closing)."""

from chipbench.readings import batches, mean


def read(run):
    held = mean([b["ended"] - b["started"] for b in batches(run)])
    engine = mean([c["end"] - c["start"] for c in run.calls])
    return None if held is None or engine is None else held - engine
