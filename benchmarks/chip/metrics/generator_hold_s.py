"""Generator (core/generator.py): mean seconds a request's pack waited
in the generator before its batch was fired, over the packs of the
window's ``colony.generator.fire`` spans."""

from chipbench.program_spans import mean_hold


def read(run):
    return mean_hold(run)
