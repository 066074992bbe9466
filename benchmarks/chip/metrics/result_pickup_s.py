"""Client and results (serve/batcher.py InferenceClient, CFS): mean
seconds from the end of the engine.generate call that computed a
request's tokens to the moment the client holds its result (the
executor's upload to CFS, then the client's polling)."""

from chipbench.readings import batch_of, call_of, mean


def read(run):
    b = batch_of(run)
    out = []
    for r in run.records:
        if r["recv_wall"] is None or r["rid"] not in b:
            continue
        call = call_of(run, b[r["rid"]])
        if call is not None:
            out.append(r["recv_wall"] - call["end"])
    return mean(out)
