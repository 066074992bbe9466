"""The comparison that decides ``correct``.

Once the window has closed and the program's state is freed, a sample of
the requests the window finished, drawn from the seed and always holding
the one with the most served tokens, goes through the plain reference:
each prompt with the tokens served for it, the whole sequence at once.
At each served position the number read is the gap by which the served
token's reference logit lies below the reference's best logit there.
Greedy decoding serves the top token of the program's own logits, so a
sound program's gaps are rounding; a wrong cache, a wrong layer or a
token altered on its way reads as a gap the size of the logits' spread.
The widest gap over the sample is held to the configuration's limit.
"""

from __future__ import annotations

import numpy as np

from . import reference
from .traffic import STREAM_SAMPLE, Request, rng_for


def sample(records: list[dict], seed: int, tokens_wanted: int) -> list[dict]:
    """Answered requests to compare: the one with the most served tokens,
    then others in an order drawn from the seed, until the sample holds
    ``tokens_wanted`` served tokens (or every answered request)."""
    answered = [r for r in records if r["tokens"] is not None and not r["failed"]]
    if not answered:
        return []
    longest = max(answered, key=lambda r: (len(r["tokens"]), -r["i"]))
    rest = [r for r in answered if r is not longest]
    order = rng_for(seed, STREAM_SAMPLE).permutation(len(rest))
    picked, total = [longest], len(longest["tokens"])
    for j in order:
        if total >= tokens_wanted:
            break
        picked.append(rest[j])
        total += len(rest[j]["tokens"])
    return sorted(picked, key=lambda r: r["i"])


def gaps(ref_logits: np.ndarray, tokens: np.ndarray) -> np.ndarray:
    """best reference logit minus the reference logit of ``tokens``."""
    best = ref_logits.max(-1)
    chosen = np.take_along_axis(ref_logits, tokens[..., None], axis=-1)[..., 0]
    return best - chosen


def compare(weights: dict, dims, requests: list[Request], picked: list[dict],
            pad_to: int, rows: int, control: bool = False) -> dict:
    """Widest gap over the served tokens of ``picked`` (and, with
    ``control``, the widest gap of the tokens the fp8 control would put
    first at the same positions). Sequences are right-padded to
    ``pad_to`` tokens so that every run compiles the same shapes;
    padding follows every position read, so causal attention never
    sees it."""
    import jax.numpy as jnp

    plen = len(requests[0].prompt)
    worst, worst_ctl, n_tokens, short = 0.0, 0.0, 0, 0
    for lo in range(0, len(picked), rows):
        chunk = picked[lo: lo + rows]
        toks = np.zeros((rows, pad_to), np.int32)
        served = []
        for j, r in enumerate(chunk):
            out = np.asarray(r["tokens"], np.int32)
            if len(out) < r["max_new"]:
                short += 1  # fewer tokens than asked for: a wrong answer
            seq = np.concatenate([requests[r["i"]].prompt, out[:-1]])[:pad_to]
            toks[j, : len(seq)] = seq
            served.append(out)
        ref = np.asarray(reference.logits(weights, dims, jnp.asarray(toks), plen - 1))
        ctl = (np.asarray(reference.logits(weights, dims, jnp.asarray(toks), plen - 1,
                                           quant=True)) if control else None)
        for j, out in enumerate(served):
            n = min(len(out), ref.shape[1])
            g = gaps(ref[j, :n], out[:n])
            worst = max(worst, float(g.max()))
            n_tokens += n
            if ctl is not None:
                gc = gaps(ref[j, :n], ctl[j, :n].argmax(-1))
                worst_ctl = max(worst_ctl, float(gc.max()))
    out = {"max_gap": worst, "tokens": n_tokens, "requests": len(picked), "short": short}
    if control:
        out["control_max_gap"] = worst_ctl
    return out
