"""Jit'd wrappers: model-facing shapes -> kernel layouts (+ auto interpret).

``interpret`` is True on the CPU backend only, so the same call sites run
the kernel bodies in Python on CPU (correctness) and compile natively on
TPU. Any other backend compiles too, and fails loudly if it cannot.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .flash_attention import flash_attention_bhsd
from .mamba_scan import mamba_chunk_scan_b
from .rwkv6 import rwkv6_chunked_bh


def _interpret() -> bool:
    return jax.default_backend() == "cpu"


@partial(jax.jit, static_argnames=("causal", "window", "block_q", "block_k"))
def flash_attention(
    q: jnp.ndarray,  # (B, S, H, D)
    k: jnp.ndarray,  # (B, S, KV, D)
    v: jnp.ndarray,
    causal: bool = True,
    window: int = 0,
    block_q: int = 128,
    block_k: int = 128,
) -> jnp.ndarray:
    b, s, h, d = q.shape
    kv = k.shape[2]
    group = h // kv
    qf = jnp.moveaxis(q, 2, 1).reshape(b * h, s, d)
    kf = jnp.moveaxis(k, 2, 1).reshape(b * kv, s, d)
    vf = jnp.moveaxis(v, 2, 1).reshape(b * kv, s, d)
    out = flash_attention_bhsd(
        qf, kf, vf, group=group, causal=causal, window=window,
        block_q=block_q, block_k=block_k, interpret=_interpret(),
    )
    return jnp.moveaxis(out.reshape(b, h, s, d), 1, 2)


@partial(jax.jit, static_argnames=("chunk",))
def rwkv6_chunked(
    r: jnp.ndarray,  # (B, T, H, K) fp32
    k: jnp.ndarray,
    v: jnp.ndarray,
    logw: jnp.ndarray,
    u: jnp.ndarray,  # (H, K)
    s0: jnp.ndarray,  # (B, H, K, V)
    chunk: int = 32,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    b, t, h, dk = r.shape
    dv = v.shape[-1]

    def flat(x):
        return jnp.moveaxis(x, 2, 1).reshape(b * h, t, x.shape[-1])

    uf = jnp.broadcast_to(u[None], (b, h, dk)).reshape(b * h, 1, dk)
    out, s_final = rwkv6_chunked_bh(
        flat(r), flat(k), flat(v), flat(logw), uf,
        s0.reshape(b * h, dk, dv).astype(jnp.float32), chunk=chunk,
        interpret=_interpret(),
    )
    out = jnp.moveaxis(out.reshape(b, h, t, dv), 1, 2)
    return out, s_final.reshape(b, h, dk, dv)


@partial(jax.jit, static_argnames=("chunk", "d_block"))
def mamba_chunk_scan(
    dt: jnp.ndarray,  # (B, T, DI) fp32
    bmat: jnp.ndarray,  # (B, T, N)
    cmat: jnp.ndarray,
    a: jnp.ndarray,  # (DI, N)
    x: jnp.ndarray,  # (B, T, DI)
    h0: jnp.ndarray,  # (B, DI, N)
    chunk: int = 64,
    d_block: int = 512,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    di = dt.shape[-1]
    d_block = min(d_block, di)
    while di % d_block:
        d_block -= 1
    return mamba_chunk_scan_b(
        dt, bmat, cmat, a, x.astype(jnp.float32), h0,
        chunk=chunk, d_block=d_block, interpret=_interpret(),
    )
