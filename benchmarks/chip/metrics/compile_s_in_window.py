"""Engine: seconds JAX spent building programs inside the traced window
(tracing, lowering and compiling, ``colony.jax.compile``): the whole
process's, the engine's programs and any other."""

from chipbench.program_spans import COMPILE, span_seconds


def read(run):
    return span_seconds(run, (COMPILE,))
