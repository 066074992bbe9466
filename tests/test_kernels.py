"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret mode)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:  # optional dependency — only the property test below needs it
    from hypothesis import given, settings, strategies as st
except ImportError:
    given = None

from repro.kernels.decode_attention import decode_attention_stacked
from repro.kernels.ops import decode_attention, flash_attention, mamba_chunk_scan, rwkv6_chunked
from repro.kernels.ref import flash_attention_ref, mamba_scan_ref, rwkv6_ref


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

FLASH_CASES = [
    # (B, S, H, KV, D, window, blocks)
    (1, 128, 4, 4, 32, 0, 64),  # MHA
    (2, 128, 4, 2, 32, 0, 64),  # GQA group 2
    (1, 256, 8, 2, 64, 0, 128),  # GQA group 4, bigger head
    (2, 128, 4, 2, 32, 48, 32),  # sliding window
    (1, 64, 2, 1, 16, 0, 16),  # tiny blocks
]


@pytest.mark.parametrize("b,s,h,kv,d,window,blk", FLASH_CASES)
def test_flash_attention_matches_ref(b, s, h, kv, d, window, blk):
    ks = jax.random.split(jax.random.key(b * s + h), 3)
    q = jax.random.normal(ks[0], (b, s, h, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, s, kv, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, s, kv, d), jnp.float32)
    out = flash_attention(q, k, v, causal=True, window=window, block_q=blk, block_k=blk)
    ref = flash_attention_ref(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=1e-4)


def test_flash_attention_bf16():
    ks = jax.random.split(jax.random.key(7), 3)
    q = jax.random.normal(ks[0], (2, 128, 4, 32), jnp.bfloat16)
    k = jax.random.normal(ks[1], (2, 128, 2, 32), jnp.bfloat16)
    v = jax.random.normal(ks[2], (2, 128, 2, 32), jnp.bfloat16)
    out = flash_attention(q, k, v, block_q=64, block_k=64)
    ref = flash_attention_ref(q, k, v)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), atol=3e-2, rtol=3e-2
    )


if given is not None:

    @settings(max_examples=8, deadline=None)
    @given(
        s_blocks=st.integers(2, 6),
        group=st.sampled_from([1, 2, 4]),
        blk=st.sampled_from([16, 32]),
        causal=st.booleans(),
    )
    def test_property_flash_attention(s_blocks, group, blk, causal):
        s = s_blocks * blk
        kv, d = 2, 16
        h = kv * group
        ks = jax.random.split(jax.random.key(s * group + blk), 3)
        q = jax.random.normal(ks[0], (1, s, h, d), jnp.float32)
        k = jax.random.normal(ks[1], (1, s, kv, d), jnp.float32)
        v = jax.random.normal(ks[2], (1, s, kv, d), jnp.float32)
        out = flash_attention(q, k, v, causal=causal, block_q=blk, block_k=blk)
        ref = flash_attention_ref(q, k, v, causal=causal)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), atol=3e-5, rtol=1e-4
        )

else:

    @pytest.mark.skip(reason="hypothesis not installed")
    def test_property_flash_attention():
        pass


# ---------------------------------------------------------------------------
# rwkv6 wkv
# ---------------------------------------------------------------------------

RWKV_CASES = [
    (1, 32, 2, 8, 16),  # (B, T, H, K, chunk)
    (2, 64, 3, 16, 16),
    (2, 96, 2, 16, 32),
]


@pytest.mark.parametrize("b,t,h,k,chunk", RWKV_CASES)
def test_rwkv6_matches_ref(b, t, h, k, chunk):
    ks = jax.random.split(jax.random.key(t + h), 5)
    r = jax.random.normal(ks[0], (b, t, h, k))
    kk = jax.random.normal(ks[1], (b, t, h, k))
    v = jax.random.normal(ks[2], (b, t, h, k))
    logw = -jnp.exp(jax.random.normal(ks[3], (b, t, h, k)))
    u = jax.random.normal(ks[4], (h, k)) * 0.2
    s0 = jnp.zeros((b, h, k, k))
    out, sf = rwkv6_chunked(r, kk, v, logw, u, s0, chunk=chunk)
    ref_o, ref_s = rwkv6_ref(r, kk, v, logw, u, s0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref_o), atol=1e-4, rtol=1e-3)
    np.testing.assert_allclose(np.asarray(sf), np.asarray(ref_s), atol=1e-4, rtol=1e-3)


def test_rwkv6_nonzero_initial_state():
    """Chunk-boundary state carry: start from a random state, not zeros."""
    b, t, h, k = 1, 32, 2, 8
    ks = jax.random.split(jax.random.key(3), 6)
    r, kk, v = (jax.random.normal(ks[i], (b, t, h, k)) for i in range(3))
    logw = -jnp.exp(jax.random.normal(ks[3], (b, t, h, k)))
    u = jax.random.normal(ks[4], (h, k)) * 0.2
    s0 = jax.random.normal(ks[5], (b, h, k, k))
    out, sf = rwkv6_chunked(r, kk, v, logw, u, s0, chunk=8)
    ref_o, ref_s = rwkv6_ref(r, kk, v, logw, u, s0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref_o), atol=1e-4, rtol=1e-3)
    np.testing.assert_allclose(np.asarray(sf), np.asarray(ref_s), atol=1e-4, rtol=1e-3)


def test_rwkv6_extreme_decay_no_overflow():
    """Log-space pairwise form: strong decay must not produce inf/nan
    (the failure mode of the exp(-cum) rescaling formulation)."""
    b, t, h, k = 1, 64, 1, 8
    ks = jax.random.split(jax.random.key(9), 3)
    r, kk, v = (jax.random.normal(ks[i], (b, t, h, k)) for i in range(3))
    logw = jnp.full((b, t, h, k), -30.0)  # near-instant forgetting
    u = jnp.zeros((h, k))
    s0 = jnp.zeros((b, h, k, k))
    out, sf = rwkv6_chunked(r, kk, v, logw, u, s0, chunk=32)
    assert np.isfinite(np.asarray(out)).all() and np.isfinite(np.asarray(sf)).all()
    ref_o, _ = rwkv6_ref(r, kk, v, logw, u, s0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref_o), atol=1e-4, rtol=1e-3)


# ---------------------------------------------------------------------------
# mamba selective scan
# ---------------------------------------------------------------------------

MAMBA_CASES = [
    (1, 64, 32, 4, 32, 32),  # (B, T, DI, N, chunk, d_block)
    (2, 128, 64, 8, 32, 32),
    (2, 64, 96, 16, 16, 48),
]


@pytest.mark.parametrize("b,t,di,n,chunk,dblk", MAMBA_CASES)
def test_mamba_scan_matches_ref(b, t, di, n, chunk, dblk):
    ks = jax.random.split(jax.random.key(di + n), 5)
    dt = jax.nn.softplus(jax.random.normal(ks[0], (b, t, di)))
    bm = jax.random.normal(ks[1], (b, t, n))
    cm = jax.random.normal(ks[2], (b, t, n))
    a = -jnp.exp(jax.random.normal(ks[3], (di, n)) * 0.5)
    x = jax.random.normal(ks[4], (b, t, di))
    h0 = jnp.zeros((b, di, n))
    y, hf = mamba_chunk_scan(dt, bm, cm, a, x, h0, chunk=chunk, d_block=dblk)
    ry, rh = mamba_scan_ref(dt, bm, cm, a, x, h0)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ry), atol=1e-4, rtol=1e-3)
    np.testing.assert_allclose(np.asarray(hf), np.asarray(rh), atol=1e-4, rtol=1e-3)


def test_mamba_nonzero_state_carry():
    b, t, di, n = 1, 32, 16, 4
    ks = jax.random.split(jax.random.key(17), 6)
    dt = jax.nn.softplus(jax.random.normal(ks[0], (b, t, di)))
    bm = jax.random.normal(ks[1], (b, t, n))
    cm = jax.random.normal(ks[2], (b, t, n))
    a = -jnp.exp(jax.random.normal(ks[3], (di, n)) * 0.5)
    x = jax.random.normal(ks[4], (b, t, di))
    h0 = jax.random.normal(ks[5], (b, di, n))
    y, hf = mamba_chunk_scan(dt, bm, cm, a, x, h0, chunk=16, d_block=16)
    ry, rh = mamba_scan_ref(dt, bm, cm, a, x, h0)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ry), atol=1e-4, rtol=1e-3)
    np.testing.assert_allclose(np.asarray(hf), np.asarray(rh), atol=1e-4, rtol=1e-3)


# ---------------------------------------------------------------------------
# decode attention over the stacked, position-minor cache
# ---------------------------------------------------------------------------

DECODE_SLOTS = 384  # three 128-lane blocks


@pytest.mark.parametrize("pos", [0, 127, 128, DECODE_SLOTS - 1])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("group,head_dim", [(1, 80), (4, 128)])  # stablelm MHA; granite GQA
def test_decode_attention_matches_gqa(group, head_dim, dtype, pos):
    """attn_decode writes slot ``pos`` of its layer and nothing else; the
    kernel (one block, and 128-position blocks) writes the same cache and
    matches ``gqa_scores_softmax_out`` on the same K/V in the (B, S, KV, D)
    layout."""
    from repro.configs import get_config
    from repro.models.attention import (
        _project_qkv, apply_rope, attn_decode, attn_spec, gqa_scores_softmax_out)
    from repro.models.sharding import init_params

    layers, b, kv, layer = 3, 2, 2, 1
    cfg = get_config("stablelm-3b", "smoke").copy(
        num_heads=kv * group, num_kv_heads=kv, head_dim=head_dim, d_model=64)
    params = init_params(jax.random.key(0), attn_spec(cfg), dtype)
    ks = jax.random.split(jax.random.key(pos + group), 3)
    x = jax.random.normal(ks[0], (b, 1, cfg.d_model), dtype)
    shape = (layers, b, kv, head_dim, DECODE_SLOTS)
    cache = {"k": jax.random.normal(ks[1], shape, dtype), "v": jax.random.normal(ks[2], shape, dtype)}

    y, new = attn_decode(params, x, cache, jnp.int32(layer), jnp.int32(pos), cfg)
    for name in ("k", "v"):
        changed = np.argwhere(np.asarray(new[name] != cache[name]))
        assert set(map(tuple, changed[:, [0, 4]])) <= {(layer, pos)}, name
        np.testing.assert_array_equal(np.delete(np.asarray(new[name]), pos, axis=4),
                                      np.delete(np.asarray(cache[name]), pos, axis=4))

    q, _, _ = _project_qkv(params, x)
    q = apply_rope(q, jnp.reshape(pos, (1,)), cfg.rope_theta)
    k_bskd = jnp.moveaxis(new["k"][layer], -1, 1)  # (B, S, KV, D), today's layout
    v_bskd = jnp.moveaxis(new["v"][layer], -1, 1)
    mask = (jnp.arange(DECODE_SLOTS) <= pos)[None, None, None, None, :]
    ref = gqa_scores_softmax_out(
        q.astype(jnp.float32), k_bskd.astype(jnp.float32), v_bskd.astype(jnp.float32), mask)
    tol = dict(atol=2e-5, rtol=1e-4) if dtype == jnp.float32 else dict(atol=3e-2, rtol=3e-2)
    qg = q.reshape(b, kv, group, head_dim)
    args = (qg, new["k"][layer, ..., pos], new["v"][layer, ..., pos], cache["k"], cache["v"],
            jnp.int32(layer), jnp.int32(pos))
    for out, k, v in (
        decode_attention(*args),
        decode_attention_stacked(*args, block_bytes=head_dim * 128 * jnp.dtype(dtype).itemsize,
                                 interpret=True),
    ):
        assert out.dtype == dtype
        np.testing.assert_array_equal(np.asarray(k), np.asarray(new["k"]))
        np.testing.assert_array_equal(np.asarray(v), np.asarray(new["v"]))
        np.testing.assert_allclose(np.asarray(out.reshape(ref.shape), np.float32),
                                   np.asarray(ref), **tol)
    y_ref = jnp.einsum("bshk,hkd->bsd", ref, params["wo"].astype(jnp.float32))
    np.testing.assert_allclose(np.asarray(y, np.float32), np.asarray(y_ref),
                               **(tol if dtype == jnp.float32 else dict(atol=1e-1, rtol=5e-2)))


@pytest.mark.parametrize("kv,d,s,itemsize,want", [
    (32, 80, 384, 2, (16, 384)),  # stablelm-3b: whole positions, half the heads
    (8, 128, 4224, 2, (1, 1408)),  # granite at 4224 slots: one head, 128-multiples
    (8, 128, 1 << 15, 2, (1, 4096)),
    (4, 16, 128, 4, (4, 128)),
])
def test_decode_block_shape(kv, d, s, itemsize, want):
    from repro.kernels.decode_attention import block_shape

    assert block_shape(kv, d, s, itemsize) == want


# ---------------------------------------------------------------------------
# model-level: use_pallas == XLA path
# ---------------------------------------------------------------------------


def test_model_forward_pallas_equals_xla():
    from repro.configs import get_config
    from repro.models import forward, init_params, model_spec

    for arch in ("mixtral-8x7b", "rwkv6-7b", "jamba-1.5-large-398b"):
        cfg = get_config(arch, "smoke").copy(
            param_dtype="float32", compute_dtype="float32"
        )
        params = init_params(jax.random.key(0), model_spec(cfg), jnp.float32)
        tokens = jax.random.randint(jax.random.key(1), (2, 16), 0, cfg.vocab_size)
        ref_logits, _ = forward(params, cfg, {"tokens": tokens})
        pal_logits, _ = forward(
            params, cfg.copy(use_pallas=True), {"tokens": tokens}
        )
        np.testing.assert_allclose(
            np.asarray(pal_logits), np.asarray(ref_logits), atol=5e-3, rtol=1e-3,
            err_msg=arch,
        )
