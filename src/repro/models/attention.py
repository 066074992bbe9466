"""GQA / sliding-window / cross attention with KV caching.

Pure-jnp paths lower on any backend, including the 512-device dry-run.
Prefill selects the Pallas flash kernel (kernels/flash_attention.py) with
``cfg.use_pallas``; decode runs the Pallas decode kernel
(kernels/decode_attention.py) wherever ``decode_kernel_serves`` observes
that it can.

Parameter spec + three entry points per block:
  * ``attn_fwd``        — full-sequence training/prefill forward
  * ``attn_decode``     — single-token decode against the stacked cache
  * ``init_attn_cache`` — cache pytree, positions minor (ring buffer when SWA bounds it)
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from ..kernels.decode_attention import LANES
from .layers import apply_rope, causal_mask
from .sharding import ParamLeaf


# ---------------------------------------------------------------------------
# Parameter spec
# ---------------------------------------------------------------------------


def attn_spec(cfg: ModelConfig, cross: bool = False) -> dict:
    d, h, kv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    spec = {
        "wq": ParamLeaf((d, h, hd), ("embed", "heads", "head_dim")),
        "wk": ParamLeaf((d, kv, hd), ("embed", "kv_heads", "head_dim")),
        "wv": ParamLeaf((d, kv, hd), ("embed", "kv_heads", "head_dim")),
        "wo": ParamLeaf((h, hd, d), ("heads", "head_dim", "embed")),
    }
    if cross:
        # K/V come from the encoder / vision memory (possibly different dim).
        mem_d = cfg.vision_embed_dim if cfg.family == "vlm" else cfg.d_model
        spec["wk"] = ParamLeaf((mem_d, kv, hd), ("vision_embed", "kv_heads", "head_dim"))
        spec["wv"] = ParamLeaf((mem_d, kv, hd), ("vision_embed", "kv_heads", "head_dim"))
        spec["gate"] = ParamLeaf((1,), (None,), init="zeros")  # llama-vision gating
    if cfg.qkv_bias:
        spec["bq"] = ParamLeaf((h, hd), ("heads", "head_dim"), init="zeros")
        spec["bk"] = ParamLeaf((kv, hd), ("kv_heads", "head_dim"), init="zeros")
        spec["bv"] = ParamLeaf((kv, hd), ("kv_heads", "head_dim"), init="zeros")
    return spec


# ---------------------------------------------------------------------------
# Core softmax attention on grouped heads
# ---------------------------------------------------------------------------


def gqa_scores_softmax_out(
    q: jnp.ndarray,  # (B, Sq, H, D)
    k: jnp.ndarray,  # (B, Skv, KV, D)
    v: jnp.ndarray,  # (B, Skv, KV, D)
    mask: jnp.ndarray | None,  # broadcastable to (B, KV, G, Sq, Skv) or (Sq, Skv)
) -> jnp.ndarray:
    b, sq, h, d = q.shape
    kv = k.shape[2]
    g = h // kv
    qg = q.reshape(b, sq, kv, g, d)
    scale = d ** -0.5
    scores = jnp.einsum("bskgd,btkd->bkgst", qg, k).astype(jnp.float32) * scale
    if mask is not None:
        scores = jnp.where(mask, scores, jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgst,btkd->bskgd", probs.astype(v.dtype), v)
    return out.reshape(b, sq, h, d)


def blockwise_attention(
    q: jnp.ndarray,  # (B, S, H, D)
    k: jnp.ndarray,  # (B, S, KV, D)
    v: jnp.ndarray,  # (B, S, KV, D)
    q_chunk: int,
    window: int = 0,
    causal: bool = True,
    rules=None,
) -> jnp.ndarray:
    """Query-block chunked causal attention (XLA flash fallback).

    Never materializes the full (B,H,S,S) score tensor: a ``lax.scan``
    over query blocks computes each block against only its visible KV
    prefix (static full-K slice; masking trims the remainder). Peak
    activation is O(B·H·q_chunk·S) instead of O(B·H·S²).

    Under sequence-parallel rules ("seq" -> "model"), the shard lands on
    the WITHIN-block q dim (the scan's block dim must stay replicated for
    local slicing), so each device computes q_chunk/16 rows per block.
    """
    from .sharding import shard_activation

    b, s, h, d = q.shape
    if q_chunk <= 0 or s % q_chunk or s <= q_chunk:
        mask = causal_mask(s, s, window=window) if causal else None
        return gqa_scores_softmax_out(q, k, v, mask)
    nblk = s // q_chunk
    qb = jnp.moveaxis(q.reshape(b, nblk, q_chunk, h, d), 1, 0)  # (nblk,B,qc,H,D)
    if rules is not None:
        qb = shard_activation(qb, (None, "batch", "seq", "heads", None), rules)

    @jax.checkpoint  # per-chunk remat: backward recomputes this chunk's
    def chunk(qi, i):  # probs instead of stacking S² residuals across chunks
        offset = i * q_chunk
        if causal:
            m = causal_mask(q_chunk, s, q_offset=offset, window=window)
        else:
            m = None
        out_i = gqa_scores_softmax_out(qi, k, v, m)
        if rules is not None:
            out_i = shard_activation(out_i, ("batch", "seq", "heads", None), rules)
        return out_i

    def body(_, inp):
        i, qi = inp
        return None, chunk(qi, i)

    _, out = jax.lax.scan(body, None, (jnp.arange(nblk), qb))
    return jnp.moveaxis(out, 0, 1).reshape(b, s, h, d)


def _project_qkv(params: dict, x: jnp.ndarray, mem: jnp.ndarray | None = None):
    src = x if mem is None else mem
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"])
    k = jnp.einsum("bsd,dhk->bshk", src, params["wk"])
    v = jnp.einsum("bsd,dhk->bshk", src, params["wv"])
    if "bq" in params:
        q = q + params["bq"]
        k = k + params["bk"]
        v = v + params["bv"]
    return q, k, v


# ---------------------------------------------------------------------------
# Full-sequence forward (training / prefill)
# ---------------------------------------------------------------------------


def attn_fwd(
    params: dict,
    x: jnp.ndarray,  # (B, S, d_model)
    cfg: ModelConfig,
    positions: jnp.ndarray,  # (S,) or (B, S)
    *,
    return_cache: bool = False,
) -> jnp.ndarray | tuple[jnp.ndarray, dict]:
    q, k, v = _project_qkv(params, x)
    if cfg.use_rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    s = x.shape[1]
    if cfg.use_pallas:
        from ..kernels.ops import flash_attention

        out = flash_attention(q, k, v, causal=True, window=cfg.sliding_window)
    else:
        from .sharding import rules_for

        out = blockwise_attention(
            q, k, v, cfg.q_chunk, window=cfg.sliding_window, causal=True,
            rules=rules_for(cfg),
        )
    y = jnp.einsum("bshk,hkd->bsd", out, params["wo"])
    if return_cache:
        return y, make_cache_from_prefill(k, v, cfg)
    return y


def cross_attn_fwd(
    params: dict,
    x: jnp.ndarray,  # (B, S, d_model)
    memory: jnp.ndarray,  # (B, M, mem_dim)
    cfg: ModelConfig,
) -> jnp.ndarray:
    q, k, v = _project_qkv(params, x, mem=memory)
    out = gqa_scores_softmax_out(q, k, v, mask=None)
    y = jnp.einsum("bshk,hkd->bsd", out, params["wo"])
    if "gate" in params:  # llama-3.2-vision: tanh-gated cross-attn residual
        y = jnp.tanh(params["gate"].astype(y.dtype)) * y
    return y


# ---------------------------------------------------------------------------
# KV cache: (B, KV, head_dim, slots), positions minor — contiguous, or a ring
# buffer under sliding-window attention. Stacked per layer it is the layout
# the decode kernel reads (kernels/decode_attention.py).
# ---------------------------------------------------------------------------


def cache_len(cfg: ModelConfig, max_len: int) -> int:
    """SWA bounds the live KV window — the decode cache is a ring buffer.
    A contiguous cache rounds its slots up to whole lane tiles: the decode
    kernel writes by tiles, and XLA stores only such slot counts
    position-minor too."""
    if cfg.sliding_window > 0:
        return min(cfg.sliding_window, max_len)
    return -(-max_len // LANES) * LANES


def _cache_shape(cfg: ModelConfig, batch: int, max_len: int) -> tuple[int, ...]:
    return (batch, cfg.num_kv_heads, cfg.head_dim, cache_len(cfg, max_len))


def init_attn_cache(cfg: ModelConfig, batch: int, max_len: int, dtype) -> dict:
    shape = _cache_shape(cfg, batch, max_len)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def abstract_attn_cache(cfg: ModelConfig, batch: int, max_len: int, dtype) -> dict:
    shape = _cache_shape(cfg, batch, max_len)
    return {"k": jax.ShapeDtypeStruct(shape, dtype), "v": jax.ShapeDtypeStruct(shape, dtype)}


def make_cache_from_prefill(k: jnp.ndarray, v: jnp.ndarray, cfg: ModelConfig) -> dict:
    """Prefill K/V (B, S, KV, D) -> decode cache (B, KV, D, slots). Under
    SWA, keep the last ``window`` positions and rotate them into ring order
    (slot = position % window) so subsequent decode writes land in the
    right slots; a contiguous cache pads to whole lane tiles."""
    s = k.shape[1]
    k, v = jnp.moveaxis(k, 1, -1), jnp.moveaxis(v, 1, -1)
    w = cfg.sliding_window
    if w > 0 and s > w:
        k = jnp.roll(k[..., -w:], shift=s % w, axis=-1)
        v = jnp.roll(v[..., -w:], shift=s % w, axis=-1)
    elif w == 0:  # whole lane tiles
        pad = [(0, 0)] * (k.ndim - 1) + [(0, cache_len(cfg, s) - s)]
        k, v = jnp.pad(k, pad), jnp.pad(v, pad)
    return {"k": k, "v": v}


def decode_kernel_serves(cfg: ModelConfig) -> bool:
    """Whether ``attn_decode`` runs the Pallas decode kernel: a contiguous
    cache in a program on one device, on a TPU or interpreted on the CPU.
    Ring buffers, programs partitioned over a mesh and other backends keep
    the XLA path."""
    from .sharding import current_activation_mesh

    return (cfg.sliding_window == 0 and current_activation_mesh() is None
            and jax.default_backend() in ("tpu", "cpu"))


def _attend_xla(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                valid: jnp.ndarray) -> jnp.ndarray:
    """q (B, KV, G, D) over one layer's k, v (B, KV, D, T) where ``valid``."""
    scores = jnp.einsum("bkgd,bkdt->bkgt", q, k).astype(jnp.float32) * q.shape[-1] ** -0.5
    scores = jnp.where(valid, scores, jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bkgt,bkdt->bkgd", probs.astype(v.dtype), v)


def attn_decode(
    params: dict,
    x_t: jnp.ndarray,  # (B, 1, d_model)
    cache: dict,  # k, v: (layers, B, KV, D, slots), every layer of the stack
    layer: jnp.ndarray,  # scalar int32 — this layer's index in the stack
    pos: jnp.ndarray,  # scalar int32 — absolute position of this token
    cfg: ModelConfig,
) -> tuple[jnp.ndarray, dict]:
    """One token against its layer of the stacked cache. The new K/V
    column is written into slot ``pos`` of that layer (the kernel writes the
    lane tile holding it; XLA a one-column update); the stack is returned
    whole."""
    q, k_t, v_t = _project_qkv(params, x_t)
    pos_arr = jnp.reshape(pos, (1,))
    if cfg.use_rope:
        q = apply_rope(q, pos_arr, cfg.rope_theta)
        k_t = apply_rope(k_t, pos_arr, cfg.rope_theta)

    ln = cache["k"].shape[-1]
    if cfg.sliding_window > 0:
        slot = pos % ln  # ring buffer — O(window) memory at any context length
    else:
        slot = jnp.minimum(pos, ln - 1)
    b, _, h, d = q.shape
    kv = cache["k"].shape[2]
    qg = q.reshape(b, kv, h // kv, d)
    if decode_kernel_serves(cfg):  # slots 0..slot are this token's context
        from ..kernels.ops import decode_attention

        out, k, v = decode_attention(qg, k_t[:, 0], v_t[:, 0], cache["k"], cache["v"], layer, slot)
    else:
        at = (layer, 0, 0, 0, slot)
        k = jax.lax.dynamic_update_slice(
            cache["k"], jnp.moveaxis(k_t, 1, -1)[None].astype(cache["k"].dtype), at)
        v = jax.lax.dynamic_update_slice(
            cache["v"], jnp.moveaxis(v_t, 1, -1)[None].astype(cache["v"].dtype), at)
        # Validity: ring slots written so far; contiguous cache positions <= pos.
        idx = jnp.arange(ln)
        if cfg.sliding_window > 0:
            valid = idx < jnp.minimum(pos + 1, ln)  # ring fully valid once wrapped
        else:
            valid = idx <= pos
        out = _attend_xla(qg, jax.lax.dynamic_index_in_dim(k, layer, keepdims=False),
                          jax.lax.dynamic_index_in_dim(v, layer, keepdims=False), valid)
    y = jnp.einsum("bshk,hkd->bsd", out.reshape(b, 1, h, d), params["wo"])
    return y, {"k": k, "v": v}
