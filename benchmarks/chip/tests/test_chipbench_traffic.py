"""Traffic of the chip benchmark: fixed by its seed, the same work in the same order for
every seed; only the prompts differ."""

import sys
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from chipbench.cell import HERE  # noqa: E402
from chipbench.traffic import output_lengths, schedule, seed32, stratified_order  # noqa: E402

import json  # noqa: E402

CHAT = json.loads((HERE / "traffic" / "chat.json").read_text())
BIG = 2**31 + 12345


def _key(reqs):
    return [(r.due_s, r.max_new_tokens, r.prompt.tobytes()) for r in reqs]


def test_same_seed_same_traffic():
    assert _key(schedule(CHAT, BIG, 30, 50304)) == _key(schedule(CHAT, BIG, 30, 50304))


def test_other_seed_other_order_same_work():
    """Another seed draws other prompts; arrivals and lengths keep the
    mix's own order, so every seed offers the same work at the same times."""
    a, b = schedule(CHAT, BIG, 30, 50304), schedule(CHAT, BIG + 1, 30, 50304)
    assert _key(a) != _key(b)
    assert not np.array_equal(a[0].prompt, b[0].prompt)
    assert [(r.due_s, r.max_new_tokens) for r in a] == [(r.due_s, r.max_new_tokens) for r in b]
    assert len(a) == len(b) == round(CHAT["rate_per_s"] * 30)



def test_arrivals_inside_the_window_at_the_rate():
    reqs = schedule(CHAT, 7, 40, 100)
    assert reqs[0].due_s == 0.0 and reqs[-1].due_s < 40
    assert all(x.due_s <= y.due_s for x, y in zip(reqs, reqs[1:]))
    assert all(len(r.prompt) == CHAT["prompt_tokens"] for r in reqs)
    assert all(0 <= r.prompt.min() and r.prompt.max() < 100 for r in reqs)


def test_lengths_lie_in_the_warmed_set():
    """Every whole length between the mix's least and most, none warmed
    ahead: the engine builds what it meets, as deployed."""
    spec = CHAT["output_tokens"]
    lengths = output_lengths(spec, 500)
    assert lengths.min() == spec["min"] and lengths.max() == spec["max"]
    assert abs(np.median(lengths) - spec["median"]) <= 1
    assert len(set(lengths.tolist())) > 100  # every whole length, no steps


def test_seed32_takes_any_whole_number():
    values = {seed32(s, 1) for s in (0, 1, BIG, 2**40)}
    assert len(values) == 4 and all(0 <= v < 2**32 for v in values)


def test_every_block_takes_one_value_of_each_stratum():
    block = CHAT["block"]
    lens = np.array([r.max_new_tokens for r in schedule(CHAT, 11, 50, 100)])
    arrivals = stratified_order(np.sort(np.random.default_rng(0).exponential(size=103)), block,
                                np.random.default_rng(1))
    for values in (lens, arrivals):
        k = len(values) // block
        strata = np.sort(values)[: k * block].reshape(block, k)
        for b in range(k):
            got = np.sort(values[b * block:(b + 1) * block])
            assert np.all((strata[:, 0] <= got) & (got <= strata[:, -1]))
