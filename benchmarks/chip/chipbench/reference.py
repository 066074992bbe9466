"""What the plain float32 references of the blocks share.

Each block module (``blocks/<block>.py``) writes its reference from the
layer equations with these helpers, not from the program: none of them
imports anything of ``src/repro``. Every matrix product runs at
``Precision.HIGHEST`` in float32. The whole sequence goes through at
once, with no cache, and attention takes its queries in blocks, so that
a reference run layer by layer fits beside nothing else on the chip.

``quant=True`` is the control: every matrix product takes its operands
rounded to float8 e4m3 (per-row scales for activations, per-column
scales for weights), the precision one step below the bf16 the
configurations state. Norms, softmax and accumulation stay float32.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

Q_BLOCK = 512
FP8_MAX = 448.0  # largest finite float8_e4m3fn


def _fq(x: jax.Array, axes: tuple[int, ...]) -> jax.Array:
    """Round to float8 e4m3 with one scale per slice over ``axes``."""
    amax = jnp.max(jnp.abs(x), axis=axes, keepdims=True)
    s = jnp.where(amax > 0, amax / FP8_MAX, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(spec: str, a: jax.Array, b: jax.Array, quant: bool) -> jax.Array:
    """einsum at HIGHEST; with ``quant``, operands rounded to fp8 along
    the axes they are summed over."""
    if quant:
        ins, out = spec.split("->")
        sa, sb = ins.split(",")
        summed = set(sa) & set(sb) - set(out)
        a = _fq(a, tuple(i for i, c in enumerate(sa) if c in summed))
        b = _fq(b, tuple(i for i, c in enumerate(sb) if c in summed))
    return jnp.einsum(spec, a, b, precision=lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)


def _norm(x: jax.Array, scale: jax.Array, bias: jax.Array | None, kind: str,
          eps: float) -> jax.Array:
    """``kind`` "layernorm" (with ``bias``) or "rmsnorm"."""
    if kind == "layernorm":
        mu = x.mean(-1, keepdims=True)
        var = ((x - mu) ** 2).mean(-1, keepdims=True)
        return (x - mu) / jnp.sqrt(var + eps) * scale + bias
    return x / jnp.sqrt((x ** 2).mean(-1, keepdims=True) + eps) * scale


def _rope(x: jax.Array, theta: float) -> jax.Array:
    """x: (B, T, heads, hd) at positions 0..T-1."""
    hd = x.shape[-1]
    inv = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _attention(q: jax.Array, k: jax.Array, v: jax.Array, quant: bool) -> jax.Array:
    """Causal attention; q (B,T,H,hd), k and v (B,T,KV,hd) -> (B,T,H,hd)."""
    b, t, h, hd = q.shape
    g = h // k.shape[2]
    k = jnp.repeat(k, g, axis=2)  # query head i reads key/value head i // g
    v = jnp.repeat(v, g, axis=2)
    outs = []
    for lo in range(0, t, Q_BLOCK):
        hi = min(lo + Q_BLOCK, t)
        s = _mm("bqhd,bkhd->bhqk", q[:, lo:hi], k[:, :hi], quant) * hd ** -0.5
        causal = jnp.arange(hi)[None, :] <= jnp.arange(lo, hi)[:, None]
        s = jnp.where(causal, s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        outs.append(_mm("bhqk,bkhd->bqhd", p, v[:, :hi], quant))
    return jnp.concatenate(outs, axis=1)


@jax.jit
def _embed(embed: jax.Array, tokens: jax.Array) -> jax.Array:
    return embed[tokens].astype(jnp.float32)
