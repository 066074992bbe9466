"""Broker (core/security.py): CPU seconds the serving process spent
signing and recovering RPC signatures (``colony.rpc.sign``,
``colony.rpc.verify``, each thread's own time, all threads summed), over
the traced window's seconds: the share of one interpreter they take."""

from chipbench.program_spans import SIGNATURES, cpu_seconds


def read(run):
    seconds = cpu_seconds(run, SIGNATURES)
    return None if seconds is None else seconds / run.trace["window_s"]
