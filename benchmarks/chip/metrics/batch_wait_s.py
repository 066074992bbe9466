"""Generator (core/generator.py): mean seconds from a request's pack
being sent to the submission of the batch process that carries it."""

from chipbench.readings import batch_of, mean


def read(run):
    b = batch_of(run)
    return mean([b[r["rid"]]["submitted"] - r["sent_wall"] for r in run.records
                 if r["sent_wall"] is not None and r["rid"] in b])
