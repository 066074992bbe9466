"""Mamba-1 selective-scan Pallas TPU kernel.

TPU adaptation of the hardware-aware CUDA scan: the recurrent state h is
VMEM scratch carried across the sequential chunk grid dimension, and the
chunk's rows are folded into it one at a time by a ``fori_loop``. HBM
traffic is dt/x (C x d_block), B/C (C x N) in and y (C x d_block) out,
never the O(T x d x N) expansion.

Grid: (batch, d_inner/d_block, T/C). d_inner is tiled so arbitrarily wide
models (jamba: 16384) keep the VMEM working set fixed. Inside the kernel
the state is laid out (N, d_block): d_block on the lanes and the SSM state
N (16) on the sublanes, so no (C x d_block x N) tensor is built; with N on
the lanes it would be padded 8x to the 128-lane tile and overflow VMEM.
B and C rows become (N, 1) columns through a small VMEM scratch, because
Mosaic cannot slice a value at a dynamic row.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _mamba_kernel(
    dt_ref, x_ref, b_ref, c_ref, a_ref, h0_ref, y_ref, hout_ref,
    h_scr, b_scr, c_scr, *, chunk: int, nchunks: int,
):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        h_scr[...] = h0_ref[0].astype(jnp.float32).T

    a = a_ref[...].astype(jnp.float32).T  # (N, Db)
    b_scr[...] = b_ref[0].astype(jnp.float32)[:, :, None]  # (C, N, 1)
    c_scr[...] = c_ref[0].astype(jnp.float32)[:, :, None]

    def row(i, h):
        dt = dt_ref[0, pl.ds(i, 1), :].astype(jnp.float32)  # (1, Db)
        x = x_ref[0, pl.ds(i, 1), :].astype(jnp.float32)
        h = jnp.exp(dt * a) * h + (dt * x) * b_scr[i]  # (N, Db)
        y = jnp.sum(h * c_scr[i], axis=0, keepdims=True)  # (1, Db)
        y_ref[0, pl.ds(i, 1), :] = y.astype(y_ref.dtype)
        return h

    h_scr[...] = jax.lax.fori_loop(0, chunk, row, h_scr[...])

    @pl.when(ic == nchunks - 1)
    def _final():
        hout_ref[0] = h_scr[...].T.astype(hout_ref.dtype)


def mamba_chunk_scan_b(
    dt: jnp.ndarray,  # (B, T, DI) fp32
    bmat: jnp.ndarray,  # (B, T, N)
    cmat: jnp.ndarray,  # (B, T, N)
    a: jnp.ndarray,  # (DI, N)
    x: jnp.ndarray,  # (B, T, DI)
    h0: jnp.ndarray,  # (B, DI, N)
    *,
    chunk: int = 64,
    d_block: int = 512,
    interpret: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    bsz, t, di = dt.shape
    n = a.shape[-1]
    chunk = min(chunk, t)
    d_block = min(d_block, di)
    assert t % chunk == 0 and di % d_block == 0, (t, chunk, di, d_block)
    nchunks = t // chunk
    nd = di // d_block
    kernel = functools.partial(_mamba_kernel, chunk=chunk, nchunks=nchunks)
    y, hout = pl.pallas_call(
        kernel,
        grid=(bsz, nd, nchunks),
        in_specs=[
            pl.BlockSpec((1, chunk, d_block), lambda b, d, c: (b, c, d)),
            pl.BlockSpec((1, chunk, d_block), lambda b, d, c: (b, c, d)),
            pl.BlockSpec((1, chunk, n), lambda b, d, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, n), lambda b, d, c: (b, c, 0)),
            pl.BlockSpec((d_block, n), lambda b, d, c: (d, 0)),
            pl.BlockSpec((1, d_block, n), lambda b, d, c: (b, d, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, d_block), lambda b, d, c: (b, c, d)),
            pl.BlockSpec((1, d_block, n), lambda b, d, c: (b, d, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, t, di), jnp.float32),
            jax.ShapeDtypeStruct((bsz, di, n), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((n, d_block), jnp.float32),
            pltpu.VMEM((chunk, n, 1), jnp.float32),
            pltpu.VMEM((chunk, n, 1), jnp.float32),
        ],
        interpret=interpret,
    )(dt, x, bmat, cmat, a, h0)
    return y, hout
