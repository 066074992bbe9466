"""Compiles for a described TPU v5e chip that is not attached.

The TPU compiler refuses what interpret mode accepts: vector slices and
primitives Mosaic cannot lower, kernels over their fast-memory budget, and
programs that do not fit the device. These tests compile the kernels and
the serving steps of the main path at real widths, with shapes only; they
run nothing. The topology is described inside a fixture, never at import.
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.flash_attention import flash_attention_bhsd
from repro.kernels.mamba_scan import mamba_chunk_scan_b
from repro.kernels.rwkv6 import rwkv6_chunked_bh
from repro.models import init_params, model_spec
from repro.models.model import abstract_cache
from repro.models.sharding import abstract_params
from repro.serve.engine import make_prefill, make_serve_step

HBM_BYTES = 16 * 2**30  # one TPU v5e chip


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _on(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("heads,kv_heads,seq,head_dim", [
    (32, 32, 2048, 80),  # stablelm-3b: MHA, d=80
    (32, 8, 2048, 128),  # GQA group 4, d=128
])
def test_flash_attention_compiles(one_chip, heads, kv_heads, seq, head_dim):
    q = _on(one_chip, (heads, seq, head_dim), jnp.bfloat16)
    kv = _on(one_chip, (kv_heads, seq, head_dim), jnp.bfloat16)
    fn = jax.jit(lambda q, k, v: flash_attention_bhsd(
        q, k, v, group=heads // kv_heads, interpret=False))
    _assert_kernel(fn.lower(q, kv, kv).compile())


def test_mamba_scan_compiles_at_jamba_width(one_chip):
    cfg = get_config("jamba-1.5-large-398b", "full")
    di, n, t = cfg.mamba.expand * cfg.d_model, cfg.mamba.state_dim, 512
    assert di == 16384
    fn = jax.jit(lambda *a: mamba_chunk_scan_b(*a, interpret=False))
    compiled = fn.lower(
        _on(one_chip, (1, t, di)), _on(one_chip, (1, t, n)), _on(one_chip, (1, t, n)),
        _on(one_chip, (di, n)), _on(one_chip, (1, t, di)), _on(one_chip, (1, di, n)),
    ).compile()
    _assert_kernel(compiled)


def test_rwkv6_compiles_at_rwkv6_7b_width(one_chip):
    cfg = get_config("rwkv6-7b", "full")
    hs = cfg.rwkv.head_size
    bh, t = cfg.d_model // hs, 512
    assert (bh, hs) == (64, 64)
    x = _on(one_chip, (bh, t, hs))
    fn = jax.jit(lambda *a: rwkv6_chunked_bh(*a, interpret=False))
    compiled = fn.lower(
        x, x, x, x, _on(one_chip, (bh, 1, hs)), _on(one_chip, (bh, hs, hs))
    ).compile()
    _assert_kernel(compiled)


def test_stablelm_3b_serve_steps_fit_one_chip(one_chip):
    """stablelm-3b ``full`` in bf16: prefill B=8 S=512 into a 1024-slot
    cache, then one decode step; each program must fit one chip's HBM."""
    cfg = get_config("stablelm-3b", "full")
    assert cfg.param_dtype == cfg.compute_dtype == "bfloat16"
    batch, seq, max_len = 8, 512, 1024

    def place(tree):
        return jax.tree.map(lambda s: _on(one_chip, s.shape, s.dtype), tree)

    params = place(jax.eval_shape(
        lambda: init_params(jax.random.key(0), model_spec(cfg), jnp.bfloat16)))
    prefill = jax.jit(make_prefill(cfg, max_len))
    tokens = {"tokens": _on(one_chip, (batch, seq), jnp.int32)}
    _, cache = jax.eval_shape(prefill, params, tokens)
    decode = jax.jit(make_serve_step(cfg))
    for compiled in (
        prefill.lower(params, tokens).compile(),
        decode.lower(params, _on(one_chip, (batch, 1), jnp.int32), place(cache),
                     _on(one_chip, (), jnp.int32)).compile(),
    ):
        m = compiled.memory_analysis()
        used = m.argument_size_in_bytes + m.temp_size_in_bytes + m.output_size_in_bytes
        assert 0 < used < HBM_BYTES, used


@pytest.mark.parametrize("arch,layers,batch,max_len", [
    ("stablelm-3b", None, 10, 384),  # chat-peak: B=10, 384 slots, MHA d=80
    ("granite-3-8b", 10, 6, 4128),  # rag: one 10-layer stage, B=6, GQA d=128
])
def test_serve_step_reads_the_cache_where_it_lies(one_chip, arch, layers, batch, max_len):
    """The engine's decode step with its cache donated: the decode kernel
    reads each layer in place, so no copy of a layer's or the whole cache's
    K or V is left, the output aliases the cache, and the temporaries stay
    under one layer's K plus V."""
    cfg = get_config(arch, "full")
    if layers:
        cfg = cfg.copy(num_layers=layers)

    def place(tree):
        return jax.tree.map(lambda s: _on(one_chip, s.shape, s.dtype), tree)

    params = place(abstract_params(model_spec(cfg), jnp.dtype(cfg.param_dtype)))
    cache = place(abstract_cache(cfg, batch, max_len))
    compiled = jax.jit(make_serve_step(cfg), donate_argnums=(2,)).lower(
        params, _on(one_chip, (batch, 1), jnp.int32), cache, _on(one_chip, (), jnp.int32)
    ).compile()
    text = compiled.as_text()
    assert text.startswith("HloModule jit_serve_step,")
    assert "tpu_custom_call" in text

    kv_leaves = [leaf for path, leaf in jax.tree_util.tree_flatten_with_path(cache)[0]
                 if jax.tree_util.keystr(path[-1:]) in ("['k']", "['v']")]
    stack = kv_leaves[0].shape  # (L, B, KV, D, S)
    layer_bytes = 2 * (kv_leaves[0].size // stack[0]) * kv_leaves[0].dtype.itemsize
    cache_bytes = sum(leaf.size * leaf.dtype.itemsize for leaf in kv_leaves)
    banned = {tuple(sorted(stack)), tuple(sorted(stack[1:]))}
    for dims in re.findall(r"= bf16\[([\d,]+)\]\S* copy(?:-start)?\(", text):
        shape = tuple(sorted(int(n) for n in dims.split(",") if n != "1"))
        assert shape not in banned, f"copy of a K/V layer or stack: bf16[{dims}]"

    m = compiled.memory_analysis()
    assert m.alias_size_in_bytes >= cache_bytes
    assert m.temp_size_in_bytes < layer_bytes
