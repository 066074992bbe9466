"""The reduction from a device trace to busy time, per-call device time
and labelled idle gaps."""

import json
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from chipbench import trace  # noqa: E402

TESTDATA = Path(__file__).resolve().parents[1] / "testdata"


def test_program_names_lose_their_hash():
    assert trace.program_name("jit_serve_step(16053567845004027975)") == "jit_serve_step"
    assert trace.program_name("jit__unstack") == "jit__unstack"


def test_synthetic_window():
    ms = 1_000_000
    ex = {
        "programs": {"0": [["jit_prefill_fn", 10 * ms, 20 * ms],
                           ["jit_serve_step", 35 * ms, 5 * ms],
                           ["jit_sample_token", 38 * ms, 4 * ms],  # overlaps the step
                           ["jit_serve_step", 60 * ms, 5 * ms],
                           ["jit_serve_step", 150 * ms, 5 * ms]]},  # after the window
        "ops": {"jit_prefill_fn/%dot = bf16[4]": 15 * ms, "jit_serve_step/%x = f32[2]": 3 * ms},
        "spans": [["bench.window", 0, 100 * ms], ["bench.engine_generate", 5 * ms, 65 * ms]],
    }
    red = trace.reduce(ex)
    assert red["window_s"] == pytest.approx(0.1)
    assert red["busy_s"] == pytest.approx(0.032)  # 20 + (35..42) + 5 ms
    assert red["calls"]["jit_serve_step"] == pytest.approx([0.005, 0.005])
    assert red["device_ops"][0] == ["jit_prefill_fn/%dot = bf16[4]", pytest.approx(0.015)]
    gaps = dict(red["idle_gaps"])
    assert gaps["bench.engine_generate: jit_prefill_fn -> jit_serve_step"] == pytest.approx(0.005)
    assert gaps["outside engine.generate: jit_serve_step -> window close"] == pytest.approx(0.035)
    assert gaps["bench.engine_generate: jit_sample_token -> jit_serve_step"] == pytest.approx(0.018)
    assert sum(gaps.values()) == pytest.approx(0.1 - 0.032)


def test_recorded_chip_trace():
    """A few calls of a traced window on a TPU v5e, with the numbers the
    reduction gave on the chip."""
    sample = json.loads((TESTDATA / "trace_stablelm-3b.chat.json").read_text())
    red = trace.reduce(sample)
    want = sample["expected"]
    assert 1 - red["busy_s"] / red["window_s"] == pytest.approx(want["device_idle_share"])
    assert 1000 * sum(red["calls"]["jit_prefill_fn"]) / len(red["calls"]["jit_prefill_fn"]) == \
        pytest.approx(want["prefill_device_ms"])
    assert 1000 * sum(red["calls"]["jit_serve_step"]) / len(red["calls"]["jit_serve_step"]) == \
        pytest.approx(want["decode_step_device_ms"])
