"""The plain reference against the program's prefill and decode through
the cache, at smoke size, for both architectures of the chip benchmark."""

import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from chipbench import blocks, check, system, weights  # noqa: E402
from chipbench.cell import HERE  # noqa: E402

CONFIGS = ["stablelm-3b", "granite-3-8b-stage"]
STEPS = 6


def config(name):
    return json.loads((HERE / "configs" / f"{name}.json").read_text())


@pytest.fixture(autouse=True)
def _registry():
    from repro.configs import ARCHS

    before = dict(ARCHS)
    yield
    ARCHS.clear()
    ARCHS.update(before)


def served(name, dtype, seed=3):
    """Greedy prefill + decode through the cache by the program's own
    jitted steps; returns its logits (B, STEPS, V), the sequences it
    made, the weights and the dims."""
    from repro.serve.engine import make_prefill, make_serve_step

    block = blocks.of(config(name))
    mc = system.model_config(config(name), "smoke").copy(param_dtype=dtype, compute_dtype=dtype)
    dims = block.dims_of(mc)
    w = weights.make(block.layout(dims), seed, jnp.dtype(dtype))
    params = block.program_params(w, dims, mc)
    s, max_len = 12, 12 + STEPS
    tokens = jnp.asarray(np.random.default_rng(seed).integers(0, dims.vocab, (2, s)), jnp.int32)
    logits, cache = jax.jit(make_prefill(mc, max_len))(params, {"tokens": tokens})
    decode = jax.jit(make_serve_step(mc))
    outs, seq = [logits[:, -1]], [tokens]
    for i in range(STEPS - 1):
        tok = jnp.argmax(outs[-1], -1)[:, None].astype(jnp.int32)
        seq.append(tok)
        logits, cache = decode(params, tok, cache, jnp.int32(s + i))
        outs.append(logits[:, -1])
    seq.append(jnp.argmax(outs[-1], -1)[:, None].astype(jnp.int32))
    return (np.asarray(jnp.stack(outs, 1), np.float32), np.asarray(jnp.concatenate(seq, 1)), w,
            block, dims)


@pytest.mark.parametrize("name", CONFIGS)
def test_reference_agrees_with_float32_program(name):
    prog, seq, w, block, dims = served(name, "float32")
    ref = np.asarray(block.logits(w, dims, jnp.asarray(seq[:, :-1]), 11))
    assert ref.shape == prog.shape
    assert np.abs(ref - prog).max() < 1e-4
    assert check.gaps(ref, seq[:, 12:]).max() == 0.0  # greedy tokens are the reference's top


@pytest.mark.parametrize("name", CONFIGS)
def test_reference_disagrees_with_lower_precision(name):
    """The program in bf16, and the reference's own fp8 control, both
    depart from the float32 reference; the control by more."""
    prog, seq, w, block, dims = served(name, "bfloat16")
    w32 = {k: v.astype(jnp.float32) for k, v in w.items()}
    toks = jnp.asarray(seq[:, :-1])
    ref = np.asarray(block.logits(w32, dims, toks, 11))
    ctl = np.asarray(block.logits(w32, dims, toks, 11, quant=True))
    bf16_err = np.abs(ref - prog).max()
    assert bf16_err > 1e-3
    assert np.abs(ref - ctl).max() > 3 * bf16_err


def test_stage_registration_keeps_published_widths():
    mc = system.model_config(config("granite-3-8b-stage"), "full")
    assert (mc.num_layers, mc.d_model, mc.num_heads, mc.num_kv_heads, mc.head_dim,
            mc.d_ff, mc.vocab_size, mc.tied_embeddings) == (10, 4096, 32, 8, 128, 12800, 49155, True)
    for name in CONFIGS:
        c = config(name)
        block = blocks.of(c)
        assert block.dims_of(system.model_config(c, "full")) == block.Dims.from_config(c)


def test_program_params_match_the_programs_tree():
    block = blocks.of(config("stablelm-3b"))
    mc = system.model_config(config("stablelm-3b"), "smoke")
    dims = block.dims_of(mc)
    w = weights.make(block.layout(dims), 1, jnp.float32)
    params = block.program_params(w, dims, mc)
    assert params["groups"]["b0"]["mlp"]["w_in"] is w["layers.w_up"]  # no copy
    with pytest.raises(ValueError):
        block.program_params(w, dims, mc.copy(d_ff=dims.d_ff + 8))
