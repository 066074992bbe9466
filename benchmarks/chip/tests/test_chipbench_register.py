"""A cut configuration is registered, and served, as itself: under its own
id, with its overrides applied into nested configs."""

import dataclasses
import json
import sys
import tempfile
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parents[1] / "src")]

from chipbench import system  # noqa: E402
from chipbench.cell import HERE, load  # noqa: E402

DEEPSEEK_SHARE = {"name": "deepseek-v3-share", "program": {
    "arch": "deepseek-v3-share", "base_arch": "deepseek-v3-671b",
    "overrides": {"moe": {"num_experts": 8}}}}


@pytest.fixture(autouse=True)
def _registry():
    from repro.configs import ARCHS

    before = dict(ARCHS)
    yield
    ARCHS.clear()
    ARCHS.update(before)


def _differences(a, b, prefix=""):
    """Dotted names of the fields in which two dataclasses differ."""
    out = []
    for f in dataclasses.fields(a):
        x, y = getattr(a, f.name), getattr(b, f.name)
        if dataclasses.is_dataclass(x):
            out += _differences(x, y, f"{prefix}{f.name}.")
        elif x != y:
            out.append(prefix + f.name)
    return out


def test_stage_is_registered_under_its_own_id():
    from repro.configs import get_config

    conf = json.loads((HERE / "configs" / "granite-3-8b-stage.json").read_text())
    assert system.register(conf) == "granite-3-8b-stage"
    mc = system.model_config(conf, "full")
    assert (mc.name, mc.num_layers) == ("granite-3-8b-stage", 10)
    assert mc == get_config("granite-3-8b-stage", "full").copy(use_pallas=False)
    assert _differences(mc, get_config("granite-3-8b", "full").copy(use_pallas=False)) == \
        ["name", "num_layers"]
    smoke = system.model_config(conf, "smoke")
    assert smoke.name == "granite-3-8b-stage"
    assert smoke.num_layers == get_config("granite-3-8b", "smoke").num_layers


def test_nested_override_changes_one_field():
    """Only the dataclass is built: no parameters."""
    mc = system.model_config(DEEPSEEK_SHARE, "full")
    base = system.model_config({"program": {"arch": "deepseek-v3-671b"}}, "full")
    assert _differences(mc, base) == ["name", "moe.num_experts"]
    assert (mc.name, mc.moe.num_experts, mc.moe.top_k) == ("deepseek-v3-share", 8, 8)
    assert base.moe.num_experts == 256


@pytest.mark.parametrize("overrides", [{"no_such_field": 1}, {"moe": {"no_such_field": 1}}])
def test_unknown_override_is_an_error(overrides):
    conf = {"program": {"arch": "cut", "base_arch": "granite-3-8b", "overrides": overrides}}
    with pytest.raises(KeyError, match="no_such_field"):
        system.model_config(conf, "full")


def test_smoke_session_serves_the_registered_config():
    from chipbench.harness import Session

    cell = load("granite-3-8b-stage.rag")
    cell.traffic = dict(cell.traffic, prompt_tokens=8, max_len=16,
                        generator={"queuesize": 1, "timeout_s": 0.5})
    with tempfile.TemporaryDirectory() as tmp:
        session = Session(cell, 5, "smoke", tmp)
        try:
            assert session.engine.cfg == system.model_config(cell.config, "smoke")
            assert session.engine.cfg.name == "granite-3-8b-stage"
        finally:
            session.close()
