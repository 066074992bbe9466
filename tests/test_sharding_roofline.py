"""Unit tests for the distribution plane: logical-axis resolution,
cache sharding fallbacks, optimizer-state specs, roofline math."""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import SHAPES, TrainConfig, get_config
from repro.configs.base import ModelConfig
from repro.configs.shapes import CellSkip, cell_skip_reason, decode_specs, input_specs
from repro.launch.mesh import make_smoke_mesh
from repro.launch.roofline import PEAK_FLOPS, Roofline, active_params, model_flops
from repro.launch.sharding_plan import cache_pspecs, opt_pspecs
from repro.models.sharding import (
    DEFAULT_RULES,
    ParamLeaf,
    param_pspecs,
    resolve_axes,
    rules_for,
)


@pytest.fixture(scope="module")
def mesh22():
    # 2x2 host mesh with the production axis names (4 CPU "devices" not
    # needed — resolve_axes/_divisible only read mesh.shape)
    import numpy as np
    from jax.sharding import Mesh

    dev = np.array(jax.devices() * 4).reshape(2, 2)
    return Mesh(dev, ("data", "model"))


def test_resolve_axes_basic(mesh22):
    assert resolve_axes(("embed", "ffn"), DEFAULT_RULES, mesh22) == P(None, "model")
    assert resolve_axes(("vocab", "embed"), DEFAULT_RULES, mesh22) == P("model", None)
    # batch -> (pod, data): pod absent on this mesh -> data only
    assert resolve_axes(("batch", None), DEFAULT_RULES, mesh22) == P(("data",), None)


def test_resolve_axes_never_repeats_mesh_axis(mesh22):
    # two logical axes mapping to "model": only the first keeps it
    spec = resolve_axes(("heads", "ffn"), DEFAULT_RULES, mesh22)
    axes = [a for a in tuple(spec) if a is not None]
    assert axes.count("model") == 1


def test_param_pspecs_divisibility(mesh22):
    spec = {
        "even": ParamLeaf((8, 4), ("embed", "ffn")),
        "odd": ParamLeaf((8, 5), ("embed", "ffn")),  # 5 % 2 != 0 -> replicated
    }
    pps = param_pspecs(spec, rules_for(get_config("stablelm-3b", "smoke")), mesh22)
    assert tuple(pps["even"])[1] == "model"
    assert tuple(pps["odd"]) == (None, None) or tuple(pps["odd"])[1] is None


def test_opt_pspecs_adamw_mirrors_params(mesh22):
    spec = {"w": ParamLeaf((8, 4), ("embed", "ffn"))}
    pps = param_pspecs(spec, DEFAULT_RULES, mesh22)
    opt = opt_pspecs(spec, pps, TrainConfig(optimizer="adamw"))
    assert opt["m"]["w"] == pps["w"] and opt["v"]["w"] == pps["w"]


def test_opt_pspecs_adafactor_drops_factored_axis(mesh22):
    spec = {"w": ParamLeaf((8, 4), ("embed", "ffn"))}
    pps = param_pspecs(spec, DEFAULT_RULES, mesh22)
    opt = opt_pspecs(spec, pps, TrainConfig(optimizer="adafactor"))
    assert opt["v"]["w"]["vr"] == P(*tuple(pps["w"])[:-1])  # row stats drop last dim


def test_cache_pspecs_kv_heads_vs_seq_fallback(mesh22):
    # kv divisible -> heads sharded; kv indivisible -> seq sharded
    cfg = get_config("stablelm-3b", "full")
    # (L, B, KV, HD, S): positions minor
    div = {"layers": {"b0": {
        "k": jax.ShapeDtypeStruct((2, 4, 2, 16, 8), jnp.bfloat16),
        "v": jax.ShapeDtypeStruct((2, 4, 2, 16, 8), jnp.bfloat16),
    }}, "memory": None}
    ps = cache_pspecs(cfg, div, mesh22)
    assert tuple(ps["layers"]["b0"]["k"])[2] == "model"  # kv=2 % 2 == 0
    odd = {"layers": {"b0": {
        "k": jax.ShapeDtypeStruct((2, 4, 3, 16, 8), jnp.bfloat16),
        "v": jax.ShapeDtypeStruct((2, 4, 3, 16, 8), jnp.bfloat16),
    }}, "memory": None}
    ps = cache_pspecs(cfg, odd, mesh22)
    assert tuple(ps["layers"]["b0"]["k"])[4] == "model"  # seq fallback


def test_cell_skip_policy():
    for arch, skipped in (
        ("qwen2.5-14b", True), ("starcoder2-15b", True), ("deepseek-v3-671b", True),
        ("rwkv6-7b", False), ("jamba-1.5-large-398b", False), ("mixtral-8x7b", False),
    ):
        cfg = get_config(arch, "full")
        reason = cell_skip_reason(cfg, SHAPES["long_500k"])
        assert (reason is not None) == skipped, arch
    with pytest.raises(CellSkip):
        input_specs(get_config("granite-3-8b", "full"), "long_500k")


def test_decode_specs_cache_matches_prefill_structure():
    """The dry-run's abstract cache tree must match what prefill returns."""
    from repro.models import init_params, model_spec, prefill

    cfg = get_config("mixtral-8x7b", "smoke").copy(
        param_dtype="float32", compute_dtype="float32"
    )
    params = init_params(jax.random.key(0), model_spec(cfg), jnp.float32)
    tokens = jnp.zeros((2, 16), jnp.int32)
    _, cache = prefill(params, cfg, {"tokens": tokens}, max_len=16)
    abstract = decode_specs(cfg, SHAPES["decode_32k"])["cache"]
    real_paths = {jax.tree_util.keystr(p) for p, _ in
                  jax.tree_util.tree_flatten_with_path(cache)[0]}
    abs_paths = {jax.tree_util.keystr(p) for p, _ in
                 jax.tree_util.tree_flatten_with_path(abstract)[0]}
    assert real_paths == abs_paths


def test_active_params_discounts_experts():
    total, active = active_params(get_config("mixtral-8x7b", "full"))
    assert active < total  # top-2 of 8
    assert active > total * 0.25  # attention/embeddings not discounted
    t2, a2 = active_params(get_config("granite-3-8b", "full"))
    assert t2 == a2  # dense: no discount


def test_model_flops_kinds():
    cfg = get_config("stablelm-3b", "full")
    train = model_flops(cfg, SHAPES["train_4k"])
    prefill = model_flops(cfg, SHAPES["prefill_32k"])
    decode = model_flops(cfg, SHAPES["decode_32k"])
    _, n = active_params(cfg)
    assert train == 6.0 * n * 256 * 4096
    assert prefill == 2.0 * n * 32 * 32768
    assert decode == 2.0 * n * 128  # one token per sequence


def test_roofline_terms_and_bottleneck():
    r = Roofline(
        flops_per_device=PEAK_FLOPS,  # exactly 1 s of compute
        bytes_per_device=819e9 * 2,  # 2 s of memory
        collective_bytes_per_device=50e9 * 0.5,  # 0.5 s of wire
        chips=4,
        model_flops_total=PEAK_FLOPS * 4,  # ideal 1 s
    )
    assert r.compute_s == pytest.approx(1.0)
    assert r.memory_s == pytest.approx(2.0)
    assert r.collective_s == pytest.approx(0.5)
    assert r.bottleneck == "memory"
    assert r.roofline_fraction == pytest.approx(0.5)  # ideal 1s / max 2s
    assert r.useful_flops_fraction == pytest.approx(1.0)
