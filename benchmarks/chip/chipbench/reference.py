"""Plain float32 reference of a dense GQA decoder, and its fp8 control.

Written from the layer equations, not from the program: it imports
nothing of ``src/repro``. It follows the architecture as the repository
declares it (the configuration files list where that departs from the
published model): pre-norm blocks; LayerNorm or RMSNorm; rotary
embedding over the whole head in rotate-half form; causal softmax
attention scaled by head_dim ** -0.5, query heads grouped over the
key/value heads; a SwiGLU MLP, silu(x W_gate) * (x W_up) W_down; a final
norm and an output head (the embedding's transpose when tied).

Every matrix product runs at ``Precision.HIGHEST`` in float32. The
whole sequence goes through at once, with no cache, one layer per call
and queries in blocks, so that it fits beside nothing else on the chip.

``quant=True`` is the control: every matrix product takes its operands
rounded to float8 e4m3 (per-row scales for activations, per-column
scales for weights), the precision one step below the bf16 the
configurations state. Norms, softmax and accumulation stay float32.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from .weights import Dims

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


def _norm(x: jax.Array, scale: jax.Array, bias: jax.Array | None, dims: Dims) -> jax.Array:
    if dims.norm == "layernorm":
        mu = x.mean(-1, keepdims=True)
        var = ((x - mu) ** 2).mean(-1, keepdims=True)
        return (x - mu) / jnp.sqrt(var + dims.norm_eps) * scale + bias
    return x / jnp.sqrt((x ** 2).mean(-1, keepdims=True) + dims.norm_eps) * scale


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


@partial(jax.jit, static_argnums=(2, 3))
def _block(w: dict, x: jax.Array, dims: Dims, quant: bool) -> jax.Array:
    w = {k: a.astype(jnp.float32) for k, a in w.items()}
    h = _norm(x, w["norm1.scale"], w.get("norm1.bias"), dims)
    q = _rope(_mm("btd,dhk->bthk", h, w["wq"], quant), dims.rope_theta)
    k = _rope(_mm("btd,dhk->bthk", h, w["wk"], quant), dims.rope_theta)
    v = _mm("btd,dhk->bthk", h, w["wv"], quant)
    x = x + _mm("bthk,hkd->btd", _attention(q, k, v, quant), w["wo"], quant)
    h = _norm(x, w["norm2.scale"], w.get("norm2.bias"), dims)
    gate = _mm("btd,df->btf", h, w["w_gate"], quant)
    up = _mm("btd,df->btf", h, w["w_up"], quant)
    return x + _mm("btf,fd->btd", jax.nn.silu(gate) * up, w["w_down"], quant)


@partial(jax.jit, static_argnums=(3, 4, 5))
def _head(w: dict, x: jax.Array, embed: jax.Array, start: int, dims: Dims, quant: bool):
    h = _norm(x[:, start:], w["norm_f.scale"].astype(jnp.float32),
              w["norm_f.bias"].astype(jnp.float32) if "norm_f.bias" in w else None, dims)
    head = embed.T if dims.tied else w["head"]
    return _mm("btd,dv->btv", h, head.astype(jnp.float32), quant)


@jax.jit
def _embed(embed: jax.Array, tokens: jax.Array) -> jax.Array:
    return embed[tokens].astype(jnp.float32)


def logits(w: dict, dims: Dims, tokens: jax.Array, start: int, quant: bool = False) -> jax.Array:
    """Logits (B, T - start, V) of positions start..T-1 of ``tokens`` (B, T)."""
    x = _embed(w["embed"], tokens)
    for layer in range(dims.layers):
        lw = {k.removeprefix("layers."): a[layer] for k, a in w.items() if k.startswith("layers.")}
        x = _block(lw, x, dims, quant)
    tail = {k: a for k, a in w.items() if k.startswith(("norm_f.", "head"))}
    return _head(tail, x, w["embed"], start, dims, quant)
