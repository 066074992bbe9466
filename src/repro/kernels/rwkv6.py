"""RWKV-6 chunked WKV Pallas TPU kernel.

TPU adaptation of the CUDA wkv6 kernel: grid is (B*H, T/C); the (K x V)
state matrix is VMEM scratch carried across the sequential chunk
dimension. Within a chunk, decay ratios are computed pairwise in log
space — exp(cum_{t-1} - cum_s) <= 1 for s < t — so the kernel never
overflows regardless of decay magnitude (the CUDA kernel's rescaling
tricks become unnecessary). All chunk-local tensors (C x K scores,
C x C attention) live in VMEM; HBM traffic is r/k/v/w in, out + final
state out.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _wkv_kernel(
    r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref, o_ref, s_final_ref, s_scr,
    *, chunk: int, nchunks: int,
):
    ic = pl.program_id(1)

    @pl.when(ic == 0)
    def _init():
        s_scr[...] = s0_ref[0].astype(jnp.float32)

    r = r_ref[0].astype(jnp.float32)  # (C, K)
    k = k_ref[0].astype(jnp.float32)  # (C, K)
    v = v_ref[0].astype(jnp.float32)  # (C, V)
    lw = w_ref[0].astype(jnp.float32)  # (C, K) = log decay, <= 0
    u = u_ref[0].astype(jnp.float32)  # (1, K) bonus

    # Inclusive prefix sum over time as a lower-triangular matmul (Mosaic
    # has no cumsum). HIGHEST keeps the f32 log-decays out of bf16 passes.
    row = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    lower = (row >= col).astype(jnp.float32)
    cum = jnp.dot(lower, lw, preferred_element_type=jnp.float32,
                  precision=jax.lax.Precision.HIGHEST)  # (C, K)
    cum_prev = cum - lw

    # Intra-chunk pairwise scores: A[t, s] = sum_k r[t]k[s]exp(cum_prev[t]-cum[s])
    diff = cum_prev[:, None, :] - cum[None, :, :]  # (C, C, K), <= 0 for s < t
    tri = jax.lax.broadcasted_iota(jnp.int32, diff.shape, 0) > \
        jax.lax.broadcasted_iota(jnp.int32, diff.shape, 1)
    ratio = jnp.where(tri, jnp.exp(diff), 0.0)
    scores = jnp.sum(r[:, None, :] * k[None, :, :] * ratio, axis=-1)  # (C, C)
    diag = jnp.sum(r * u * k, axis=1)  # (C,) bonus term
    out = jnp.dot(scores, v, preferred_element_type=jnp.float32)
    out = out + diag[:, None] * v

    # Cross-chunk: r decayed against incoming state
    s0 = s_scr[...]  # (K, V)
    rw = r * jnp.exp(cum_prev)  # (C, K)
    out = out + jnp.dot(rw, s0, preferred_element_type=jnp.float32)

    # State update: S' = diag(exp(cum_C)) S + sum_s exp(cum_C - cum_s) k_s v_s
    tail = jnp.exp(cum[chunk - 1:chunk] - cum)  # (C, K)
    s_scr[...] = jnp.exp(cum[chunk - 1])[:, None] * s0 + jnp.dot(
        (k * tail).T, v, preferred_element_type=jnp.float32
    )

    o_ref[0] = out.astype(o_ref.dtype)

    @pl.when(ic == nchunks - 1)
    def _final():
        s_final_ref[0] = s_scr[...].astype(s_final_ref.dtype)


def rwkv6_chunked_bh(
    r: jnp.ndarray,  # (BH, T, K) fp32
    k: jnp.ndarray,
    v: jnp.ndarray,  # (BH, T, V)
    logw: jnp.ndarray,  # (BH, T, K)
    u: jnp.ndarray,  # (BH, 1, K) per-head bonus (pre-broadcast)
    s0: jnp.ndarray,  # (BH, K, V) incoming state
    *,
    chunk: int = 32,
    interpret: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    bh, t, dk = r.shape
    dv = v.shape[-1]
    chunk = min(chunk, t)
    assert t % chunk == 0, (t, chunk)
    nchunks = t // chunk
    kernel = functools.partial(_wkv_kernel, chunk=chunk, nchunks=nchunks)
    out, s_final = pl.pallas_call(
        kernel,
        grid=(bh, nchunks),
        in_specs=[
            pl.BlockSpec((1, chunk, dk), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, dk), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, dv), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, dk), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, 1, dk), lambda b, c: (b, 0, 0)),
            pl.BlockSpec((1, dk, dv), lambda b, c: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, dv), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, dk, dv), lambda b, c: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, t, dv), r.dtype),
            jax.ShapeDtypeStruct((bh, dk, dv), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((dk, dv), jnp.float32)],
        interpret=interpret,
    )(r, k, v, logw, u, s0)
    return out, s_final
