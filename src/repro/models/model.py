"""Unified model assembly for all 10 assigned architectures.

An architecture is a *layout*: a repeating group of block definitions
(the scan unit) tiled ``num_groups`` times, plus embedding/head and an
optional encoder (seamless) or cross-attention memory (llama-vision).

  dense/moe LM : group = [attn + mlp|moe]                 (x num_layers)
  deepseek-v3  : group = [mla + moe(shared+routed)]       (x 61)
  jamba        : group of 8, attn at index 4, moe on odd  (x 9)
  rwkv6        : group = [time-mix + channel-mix]         (x 32)
  llama-vision : group of 5, cross-attn layer at index 0  (x 8)
  seamless     : 24-layer encoder + 24 x [attn+xattn+mlp] decoder

Every block provides: params spec, full-seq forward (training/prefill,
optionally returning a decode cache) and a single-token decode step.
Scan-over-groups keeps HLO size depth-independent; remat policy applies
per scanned group.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from . import attention as attn
from . import mla as mla_mod
from . import moe as moe_mod
from . import rwkv as rwkv_mod
from . import ssm as ssm_mod
from .layers import activate, apply_norm
from .sharding import ParamLeaf, shard_activation

# ---------------------------------------------------------------------------
# Layouts
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class BlockDef:
    mixer: str  # attn | mla | mamba | rwkv | xattn
    mlp: str  # dense | moe | rwkv_cm | none
    causal: bool = True


@dataclass(frozen=True)
class Layout:
    group: tuple[BlockDef, ...]
    num_groups: int

    @property
    def num_layers(self) -> int:
        return len(self.group) * self.num_groups


def decoder_layout(cfg: ModelConfig) -> Layout:
    moe_on = cfg.moe.num_experts > 0
    if cfg.family == "ssm":
        return Layout((BlockDef("rwkv", "rwkv_cm"),), cfg.num_layers)
    if cfg.hybrid_period > 0:  # jamba
        blocks = []
        for i in range(cfg.hybrid_period):
            mixer = "attn" if i == cfg.hybrid_attn_index else "mamba"
            mlp = "moe" if (moe_on and i % cfg.moe.moe_every == 1) else "dense"
            blocks.append(BlockDef(mixer, mlp))
        return Layout(tuple(blocks), cfg.num_layers // cfg.hybrid_period)
    if cfg.cross_attn_every > 0:  # llama-3.2-vision
        blocks = [BlockDef("xattn", "dense")]
        blocks += [BlockDef("attn", "dense")] * (cfg.cross_attn_every - 1)
        return Layout(tuple(blocks), cfg.num_layers // cfg.cross_attn_every)
    mixer = "mla" if cfg.attention == "mla" else "attn"
    if moe_on and cfg.moe.moe_every > 1:
        blocks = tuple(
            BlockDef(mixer, "moe" if i % cfg.moe.moe_every == 0 else "dense")
            for i in range(cfg.moe.moe_every)
        )
        return Layout(blocks, cfg.num_layers // cfg.moe.moe_every)
    return Layout((BlockDef(mixer, "moe" if moe_on else "dense"),), cfg.num_layers)


def encoder_layout(cfg: ModelConfig) -> Layout:
    return Layout((BlockDef("attn", "dense", causal=False),), cfg.encoder_layers)


def prefix_layout(cfg: ModelConfig) -> Layout:
    """Dense-MLP prefix layers (deepseek: first 3 of 61)."""
    mixer = "mla" if cfg.attention == "mla" else "attn"
    return Layout((BlockDef(mixer, "dense"),), cfg.dense_prefix_layers)


def prefix_cfg(cfg: ModelConfig) -> ModelConfig:
    from ..configs.base import MoEConfig

    return cfg.copy(d_ff=cfg.prefix_d_ff or cfg.d_ff, moe=MoEConfig())


def decoder_with_cross_layout(cfg: ModelConfig) -> Layout:
    """Seamless decoder: self-attn + cross-attn + mlp per layer."""
    return Layout((BlockDef("attn_x", "dense"),), cfg.num_layers)


# ---------------------------------------------------------------------------
# Parameter specs
# ---------------------------------------------------------------------------


def _norm_spec(cfg: ModelConfig, dim: int | None = None) -> dict:
    d = dim if dim is not None else cfg.d_model
    spec = {"scale": ParamLeaf((d,), ("embed_noshard",), init="ones")}
    if cfg.norm == "layernorm":
        spec["bias"] = ParamLeaf((d,), ("embed_noshard",), init="zeros")
    return spec


def _mlp_spec(cfg: ModelConfig) -> dict:
    d, f = cfg.d_model, cfg.d_ff
    spec = {
        "w_in": ParamLeaf((d, f), ("embed", "ffn")),
        "w_out": ParamLeaf((f, d), ("ffn", "embed")),
    }
    if cfg.activation in ("swiglu", "geglu"):
        spec["w_gate"] = ParamLeaf((d, f), ("embed", "ffn"))
    return spec


def _block_spec(bdef: BlockDef, cfg: ModelConfig) -> dict:
    spec: dict[str, Any] = {"norm1": _norm_spec(cfg)}
    if bdef.mixer == "attn":
        spec["mixer"] = attn.attn_spec(cfg)
    elif bdef.mixer == "attn_x":
        spec["mixer"] = attn.attn_spec(cfg)
        spec["xattn"] = attn.attn_spec(cfg, cross=True)
        spec["norm_x"] = _norm_spec(cfg)
    elif bdef.mixer == "xattn":
        spec["mixer"] = attn.attn_spec(cfg, cross=True)
    elif bdef.mixer == "mla":
        spec["mixer"] = mla_mod.mla_spec(cfg)
    elif bdef.mixer == "mamba":
        spec["mixer"] = ssm_mod.mamba_spec(cfg)
    elif bdef.mixer == "rwkv":
        spec["mixer"] = rwkv_mod.rwkv_time_mix_spec(cfg)
    else:
        raise ValueError(bdef.mixer)
    if bdef.mlp == "dense":
        spec["mlp"] = _mlp_spec(cfg)
        spec["norm2"] = _norm_spec(cfg)
    elif bdef.mlp == "moe":
        spec["mlp"] = moe_mod.moe_spec(cfg)
        spec["norm2"] = _norm_spec(cfg)
    elif bdef.mlp == "rwkv_cm":
        spec["mlp"] = rwkv_mod.rwkv_channel_mix_spec(cfg)
        spec["norm2"] = _norm_spec(cfg)
    elif bdef.mlp == "none":
        pass
    else:
        raise ValueError(bdef.mlp)
    return spec


def _group_spec(layout: Layout, cfg: ModelConfig) -> dict:
    return {f"b{i}": _block_spec(b, cfg) for i, b in enumerate(layout.group)}


def model_spec(cfg: ModelConfig) -> dict:
    from .sharding import stack_spec

    layout = decoder_layout(cfg) if not cfg.is_encdec else decoder_with_cross_layout(cfg)
    spec: dict[str, Any] = {
        "embed": ParamLeaf((cfg.vocab_size, cfg.d_model), ("vocab", "embed"), init="embed"),
        "groups": stack_spec(_group_spec(layout, cfg), layout.num_groups),
        "norm_f": _norm_spec(cfg),
    }
    if not cfg.tied_embeddings:
        spec["lm_head"] = ParamLeaf((cfg.d_model, cfg.vocab_size), ("embed", "vocab"))
    if cfg.dense_prefix_layers > 0:
        pre = prefix_layout(cfg)
        spec["prefix_groups"] = stack_spec(
            _group_spec(pre, prefix_cfg(cfg)), pre.num_groups
        )
    if cfg.is_encdec:
        enc = encoder_layout(cfg)
        spec["encoder"] = {
            "proj": ParamLeaf((cfg.audio_embed_dim, cfg.d_model), ("vision_embed", "embed")),
            "groups": stack_spec(_group_spec(enc, cfg), enc.num_groups),
            "norm_f": _norm_spec(cfg),
        }
    if cfg.mtp_depth > 0:
        mtp_block = _block_spec(BlockDef("mla" if cfg.attention == "mla" else "attn", "dense"), cfg)
        spec["mtp"] = {
            "proj": ParamLeaf((2 * cfg.d_model, cfg.d_model), ("embed_noshard", "embed")),
            "norm_in": _norm_spec(cfg),
            "block": mtp_block,
        }
    return spec


# ---------------------------------------------------------------------------
# Forward (training / prefill)
# ---------------------------------------------------------------------------


def _mixer_fwd(bdef: BlockDef, bparams: dict, h: jnp.ndarray, cfg: ModelConfig,
               positions: jnp.ndarray, memory: jnp.ndarray | None,
               return_cache: bool):
    """Returns (out, cache_or_None)."""
    if bdef.mixer in ("attn", "attn_x"):
        if bdef.causal:
            res = attn.attn_fwd(bparams["mixer"], h, cfg, positions, return_cache=return_cache)
        else:  # bidirectional encoder attention
            res = _bidir_attn(bparams["mixer"], h, cfg, positions, return_cache)
        return res if return_cache else (res, None)
    if bdef.mixer == "xattn":
        out = attn.cross_attn_fwd(bparams["mixer"], h, memory, cfg)
        if return_cache:
            return out, _cross_cache(bparams["mixer"], memory)
        return out, None
    if bdef.mixer == "mla":
        res = mla_mod.mla_fwd(bparams["mixer"], h, cfg, positions, return_cache=return_cache)
        return res if return_cache else (res, None)
    if bdef.mixer == "mamba":
        res = ssm_mod.mamba_fwd(bparams["mixer"], h, cfg, return_cache=return_cache)
        return res if return_cache else (res, None)
    if bdef.mixer == "rwkv":
        res = rwkv_mod.rwkv_time_mix_fwd(bparams["mixer"], h, cfg, return_cache=return_cache)
        return res if return_cache else (res, None)
    raise ValueError(bdef.mixer)


def _bidir_attn(params: dict, h: jnp.ndarray, cfg: ModelConfig, positions, return_cache):
    q, k, v = attn._project_qkv(params, h)
    q = attn.apply_rope(q, positions, cfg.rope_theta)
    k = attn.apply_rope(k, positions, cfg.rope_theta)
    out = attn.gqa_scores_softmax_out(q, k, v, mask=None)
    y = jnp.einsum("bshk,hkd->bsd", out, params["wo"])
    if return_cache:
        return y, {"k": k, "v": v}
    return y


def _cross_cache(params: dict, memory: jnp.ndarray) -> dict:
    k = jnp.einsum("bsd,dhk->bshk", memory, params["wk"])
    v = jnp.einsum("bsd,dhk->bshk", memory, params["wv"])
    return {"xk": k, "xv": v}


def _mlp_fwd(bdef: BlockDef, bparams: dict, h: jnp.ndarray, cfg: ModelConfig,
             state: dict | None = None, return_cache: bool = False):
    """Returns (out, aux, cache)."""
    zero = jnp.zeros((), jnp.float32)
    if bdef.mlp == "dense":
        gate = jnp.einsum("bsd,df->bsf", h, bparams["mlp"].get("w_gate", bparams["mlp"]["w_in"]))
        up = jnp.einsum("bsd,df->bsf", h, bparams["mlp"]["w_in"]) if "w_gate" in bparams["mlp"] else None
        act = activate(gate, up, cfg.activation)
        act = shard_activation(act, ("batch", "seq", "ffn"), _current_rules(cfg))
        out = jnp.einsum("bsf,fd->bsd", act, bparams["mlp"]["w_out"])
        return out, {"lb_loss": zero, "z_loss": zero}, None
    if bdef.mlp == "moe":
        out, aux = moe_mod.moe_fwd(bparams["mlp"], h, cfg)
        return out, {"lb_loss": aux["lb_loss"], "z_loss": aux["z_loss"]}, None
    if bdef.mlp == "rwkv_cm":
        if return_cache:
            out, cm_state = rwkv_mod.rwkv_channel_mix_fwd(
                bparams["mlp"], h, cfg, state=state, return_cache=True
            )
            return out, {"lb_loss": zero, "z_loss": zero}, cm_state
        out = rwkv_mod.rwkv_channel_mix_fwd(bparams["mlp"], h, cfg, state=state)
        return out, {"lb_loss": zero, "z_loss": zero}, None
    return jnp.zeros_like(h), {"lb_loss": zero, "z_loss": zero}, None


def _current_rules(cfg: ModelConfig):
    from .sharding import rules_for

    return rules_for(cfg)


def _block_fwd(bdef: BlockDef, bparams: dict, x: jnp.ndarray, cfg: ModelConfig,
               positions: jnp.ndarray, memory: jnp.ndarray | None,
               return_cache: bool):
    """Pre-norm residual block. Returns (x, aux, cache)."""
    cache: dict = {}
    h = apply_norm(x, bparams["norm1"], cfg.norm, cfg.norm_eps)
    out, c = _mixer_fwd(bdef, bparams, h, cfg, positions, memory, return_cache)
    if c:
        cache.update(c)
    x = x + out
    if bdef.mixer == "attn_x":  # seamless decoder cross-attn sub-layer
        h = apply_norm(x, bparams["norm_x"], cfg.norm, cfg.norm_eps)
        out = attn.cross_attn_fwd(bparams["xattn"], h, memory, cfg)
        if return_cache:
            cache.update(_cross_cache(bparams["xattn"], memory))
        x = x + out
    aux = {"lb_loss": jnp.zeros((), jnp.float32), "z_loss": jnp.zeros((), jnp.float32)}
    if bdef.mlp != "none":
        h = apply_norm(x, bparams["norm2"], cfg.norm, cfg.norm_eps)
        out, aux, mlp_cache = _mlp_fwd(bdef, bparams, h, cfg, return_cache=return_cache)
        if mlp_cache:
            cache["cm"] = mlp_cache
        x = x + out
    x = shard_activation(x, ("batch", "seq", "embed_noshard"), _current_rules(cfg))
    return x, aux, cache


def _group_fwd(layout: Layout, gparams: dict, x: jnp.ndarray, cfg: ModelConfig,
               positions: jnp.ndarray, memory: jnp.ndarray | None,
               return_cache: bool):
    caches = {}
    aux_sum = {"lb_loss": jnp.zeros((), jnp.float32), "z_loss": jnp.zeros((), jnp.float32)}
    for i, bdef in enumerate(layout.group):
        x, aux, cache = _block_fwd(
            bdef, gparams[f"b{i}"], x, cfg, positions, memory, return_cache
        )
        aux_sum = {k: aux_sum[k] + aux[k] for k in aux_sum}
        caches[f"b{i}"] = cache
    return x, aux_sum, caches


def _run_stack(layout: Layout, groups_params: dict, x: jnp.ndarray, cfg: ModelConfig,
               positions: jnp.ndarray, memory: jnp.ndarray | None,
               return_cache: bool):
    """Scan the group stack. groups_params leaves have leading num_groups axis."""
    zero_aux = {"lb_loss": jnp.zeros((), jnp.float32), "z_loss": jnp.zeros((), jnp.float32)}

    def body(carry, gparams):
        x, aux_sum = carry
        x, aux, caches = _group_fwd(layout, gparams, x, cfg, positions, memory, return_cache)
        aux_sum = {k: aux_sum[k] + aux[k] for k in aux_sum}
        return (x, aux_sum), caches

    if cfg.remat == "full":
        body = jax.checkpoint(body, prevent_cse=False)
    elif cfg.remat == "dots":
        body = jax.checkpoint(
            body,
            policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims,
            prevent_cse=False,
        )

    if cfg.scan_layers and layout.num_groups > 1:
        (x, aux), caches = jax.lax.scan(body, (x, zero_aux), groups_params)
        return x, aux, caches  # cache leaves: (num_groups, ...)
    # unrolled
    aux_sum = zero_aux
    all_caches = []
    for g in range(layout.num_groups):
        gparams = jax.tree.map(lambda p: p[g], groups_params)
        (x, aux_sum), caches = body((x, aux_sum), gparams)
        all_caches.append(caches)
    if return_cache and all_caches:
        caches = jax.tree.map(lambda *xs: jnp.stack(xs), *all_caches)
    else:
        caches = {}
    return x, aux_sum, caches


def _encode(params: dict, cfg: ModelConfig, frames: jnp.ndarray) -> jnp.ndarray:
    enc = encoder_layout(cfg)
    x = jnp.einsum("bsa,ad->bsd", frames, params["encoder"]["proj"])
    positions = jnp.arange(x.shape[1])
    x, _, _ = _run_stack(enc, params["encoder"]["groups"], x, cfg, positions, None, False)
    return apply_norm(x, params["encoder"]["norm_f"], cfg.norm, cfg.norm_eps)


def _memory_from_batch(params: dict, cfg: ModelConfig, batch: dict) -> jnp.ndarray | None:
    if cfg.is_encdec:
        return _encode(params, cfg, batch["src_frames"].astype(_cdtype(cfg)))
    if cfg.cross_attn_every > 0:
        return batch["image_embeds"].astype(_cdtype(cfg))
    return None


def _cdtype(cfg: ModelConfig):
    return jnp.dtype(cfg.compute_dtype)


def _logits(params: dict, cfg: ModelConfig, x: jnp.ndarray) -> jnp.ndarray:
    if cfg.tied_embeddings:
        head = params["embed"].T
    else:
        head = params["lm_head"]
    return jnp.einsum("bsd,dv->bsv", x, head).astype(jnp.dtype(cfg.logits_dtype))


def forward(params: dict, cfg: ModelConfig, batch: dict,
            *, return_cache: bool = False, return_hidden: bool = False):
    """Full-sequence forward. batch: {"tokens": (B,S) int32, ...extras}.

    Returns (logits, aux[, cache][, hidden]).
    """
    tokens = batch["tokens"]
    layout = decoder_layout(cfg) if not cfg.is_encdec else decoder_with_cross_layout(cfg)
    x = params["embed"][tokens].astype(_cdtype(cfg))
    x = shard_activation(x, ("batch", "seq", "embed_noshard"), _current_rules(cfg))
    positions = jnp.arange(tokens.shape[1])
    memory = _memory_from_batch(params, cfg, batch)
    prefix_caches = {}
    if cfg.dense_prefix_layers > 0:
        x, _, prefix_caches = _run_stack(
            prefix_layout(cfg), params["prefix_groups"], x, prefix_cfg(cfg),
            positions, memory, return_cache,
        )
    x, aux, caches = _run_stack(layout, params["groups"], x, cfg, positions, memory, return_cache)
    hidden = apply_norm(x, params["norm_f"], cfg.norm, cfg.norm_eps)
    logits = _logits(params, cfg, hidden)
    out = [logits, aux]
    if return_cache:
        cache_out = {"layers": caches, "memory": memory}
        if cfg.dense_prefix_layers > 0:
            cache_out["prefix_layers"] = prefix_caches
        out.append(cache_out)
    if return_hidden:
        out.append(hidden)
    return tuple(out)


def mtp_logits(params: dict, cfg: ModelConfig, hidden: jnp.ndarray, tokens: jnp.ndarray):
    """DeepSeek MTP: predict token t+2 from (hidden_t, embed(token_{t+1}))."""
    mtp = params["mtp"]
    h = hidden[:, :-1]  # positions 0..S-2
    nxt = params["embed"][tokens[:, 1:]].astype(h.dtype)  # embed of t+1
    both = jnp.concatenate([apply_norm(h, mtp["norm_in"], cfg.norm, cfg.norm_eps), nxt], axis=-1)
    x = jnp.einsum("bsk,kd->bsd", both, mtp["proj"])
    bdef = BlockDef("mla" if cfg.attention == "mla" else "attn", "dense")
    positions = jnp.arange(x.shape[1])
    x, _, _ = _block_fwd(bdef, mtp["block"], x, cfg, positions, None, False)
    return _logits(params, cfg, x)  # aligned with targets t+2


# ---------------------------------------------------------------------------
# Decode path
# ---------------------------------------------------------------------------


def _block_abstract_cache(bdef: BlockDef, cfg: ModelConfig, batch: int, max_len: int, dtype, mem_len: int):
    cache: dict[str, Any] = {}
    if bdef.mixer in ("attn", "attn_x"):
        cache.update(attn.abstract_attn_cache(cfg, batch, max_len, dtype))
    if bdef.mixer in ("xattn", "attn_x"):
        kv, hd = cfg.num_kv_heads, cfg.head_dim
        cache["xk"] = jax.ShapeDtypeStruct((batch, mem_len, kv, hd), dtype)
        cache["xv"] = jax.ShapeDtypeStruct((batch, mem_len, kv, hd), dtype)
    if bdef.mixer == "mla":
        cache.update(mla_mod.abstract_mla_cache(cfg, batch, max_len, dtype))
    if bdef.mixer == "mamba":
        cache.update(ssm_mod.abstract_mamba_cache(cfg, batch, dtype))
    if bdef.mixer == "rwkv":
        rc = rwkv_mod.abstract_rwkv_cache(cfg, batch, dtype)
        cache.update(rc["tm"])
    if bdef.mlp == "rwkv_cm":
        cache["cm"] = {"x_prev": jax.ShapeDtypeStruct((batch, cfg.d_model), dtype)}
    return cache


def abstract_cache(cfg: ModelConfig, batch: int, max_len: int, mem_len: int = 0):
    """ShapeDtypeStruct cache pytree matching prefill's return structure."""
    layout = decoder_layout(cfg) if not cfg.is_encdec else decoder_with_cross_layout(cfg)
    dtype = _cdtype(cfg)

    def group_stack(lo: Layout) -> dict:
        gc = {
            f"b{i}": _block_abstract_cache(b, cfg, batch, max_len, dtype, mem_len)
            for i, b in enumerate(lo.group)
        }
        return jax.tree.map(
            lambda s: jax.ShapeDtypeStruct((lo.num_groups,) + s.shape, s.dtype), gc
        )

    out = {"layers": group_stack(layout)}
    if cfg.dense_prefix_layers > 0:
        out["prefix_layers"] = group_stack(prefix_layout(cfg))
    if cfg.is_encdec or cfg.cross_attn_every > 0:
        mdim = cfg.d_model if cfg.is_encdec else cfg.vision_embed_dim
        out["memory"] = jax.ShapeDtypeStruct((batch, mem_len, mdim), dtype)
    else:
        out["memory"] = None
    return out


def _block_decode(bdef: BlockDef, bparams: dict, x: jnp.ndarray, cache: dict,
                  layer: jnp.ndarray, pos: jnp.ndarray, cfg: ModelConfig,
                  memory: jnp.ndarray | None):
    """``cache`` holds this layer's slices, except self-attention K/V: the
    whole stack, of which ``layer`` is this block's."""
    new_cache = dict(cache)
    h = apply_norm(x, bparams["norm1"], cfg.norm, cfg.norm_eps)
    if bdef.mixer in ("attn", "attn_x"):
        out, upd = attn.attn_decode(
            bparams["mixer"], h, {"k": cache["k"], "v": cache["v"]}, layer, pos, cfg)
        new_cache.update(upd)
    elif bdef.mixer == "xattn":
        out = _xattn_decode(bparams["mixer"], h, cache)
    elif bdef.mixer == "mla":
        out, upd = mla_mod.mla_decode(
            bparams["mixer"], h, {"c_kv": cache["c_kv"], "k_rope": cache["k_rope"]}, pos, cfg
        )
        new_cache.update(upd)
    elif bdef.mixer == "mamba":
        out, upd = ssm_mod.mamba_decode(bparams["mixer"], h, {"h": cache["h"], "conv": cache["conv"]}, cfg)
        new_cache.update(upd)
    elif bdef.mixer == "rwkv":
        out, upd = rwkv_mod.rwkv_time_mix_decode(
            bparams["mixer"], h, {"wkv": cache["wkv"], "x_prev": cache["x_prev"]}, cfg
        )
        new_cache.update(upd)
    else:
        raise ValueError(bdef.mixer)
    x = x + out
    if bdef.mixer == "attn_x":
        h = apply_norm(x, bparams["norm_x"], cfg.norm, cfg.norm_eps)
        out = _xattn_decode(bparams["xattn"], h, cache)
        x = x + out
    if bdef.mlp != "none":
        h = apply_norm(x, bparams["norm2"], cfg.norm, cfg.norm_eps)
        if bdef.mlp == "rwkv_cm":
            out, cm = rwkv_mod.rwkv_channel_mix_decode(bparams["mlp"], h, cache["cm"], cfg)
            new_cache["cm"] = cm
        else:
            out, _, _ = _mlp_fwd(bdef, bparams, h, cfg)
        x = x + out
    return x, new_cache


def _xattn_decode(params: dict, h: jnp.ndarray, cache: dict) -> jnp.ndarray:
    q = jnp.einsum("bsd,dhk->bshk", h, params["wq"])
    if "bq" in params:
        q = q + params["bq"]
    out = attn.gqa_scores_softmax_out(q, cache["xk"], cache["xv"], mask=None)
    y = jnp.einsum("bshk,hkd->bsd", out, params["wo"])
    if "gate" in params:
        y = jnp.tanh(params["gate"].astype(y.dtype)) * y
    return y


def _decode_stack(layout: Layout, groups_params: dict, layer_caches: dict,
                  x: jnp.ndarray, pos: jnp.ndarray, cfg: ModelConfig,
                  memory: jnp.ndarray | None):
    """Decode through the groups. Self-attention K/V ride in the carry as
    whole stacks, each layer writing its new column in place and reading
    its own layer where it lies; every other cache leaf is sliced per group
    and stacked back."""
    kv_keys = ("k", "v")
    carried = {n: {k: c[k] for k in kv_keys} for n, c in layer_caches.items() if "k" in c}
    sliced = {n: {k: leaf for k, leaf in c.items() if n not in carried or k not in kv_keys}
              for n, c in layer_caches.items()}

    def body(carry, xs):
        x, kv = carry
        gparams, gcache, layer = xs
        kv, new_caches = dict(kv), {}
        for i, bdef in enumerate(layout.group):
            name = f"b{i}"
            x, nc = _block_decode(bdef, gparams[name], x, {**gcache[name], **kv.get(name, {})},
                                  layer, pos, cfg, memory)
            if name in kv:
                kv[name] = {k: nc.pop(k) for k in kv_keys}
            new_caches[name] = nc
        return (x, kv), new_caches

    # The depth as the parameters give it, which is what the scan walks.
    depth = jax.tree.leaves(groups_params)[0].shape[0]
    layers = jnp.arange(depth, dtype=jnp.int32)
    if cfg.scan_layers and layout.num_groups > 1:
        (x, carried), sliced = jax.lax.scan(body, (x, carried), (groups_params, sliced, layers))
    else:
        outs, carry = [], (x, carried)
        for g in range(depth):
            gparams, gcache = jax.tree.map(lambda p: p[g], (groups_params, sliced))
            carry, nc = body(carry, (gparams, gcache, layers[g]))
            outs.append(nc)
        (x, carried) = carry
        sliced = jax.tree.map(lambda *xs: jnp.stack(xs), *outs)
    return x, {n: {**sliced[n], **carried.get(n, {})} for n in layer_caches}


def decode_uses_kernel(cfg: ModelConfig) -> bool:
    """Whether ``decode_step`` runs the Pallas decode kernel in any layer."""
    layouts = [decoder_layout(cfg) if not cfg.is_encdec else decoder_with_cross_layout(cfg)]
    if cfg.dense_prefix_layers > 0:
        layouts.append(prefix_layout(cfg))
    return attn.decode_kernel_serves(cfg) and any(
        b.mixer in ("attn", "attn_x") for lo in layouts for b in lo.group)


def decode_step(params: dict, cfg: ModelConfig, tokens: jnp.ndarray,
                cache: dict, pos: jnp.ndarray):
    """One token for the whole batch. tokens: (B,1). Returns (logits, cache)."""
    layout = decoder_layout(cfg) if not cfg.is_encdec else decoder_with_cross_layout(cfg)
    x = params["embed"][tokens].astype(_cdtype(cfg))
    memory = cache.get("memory")

    new_cache = {"memory": memory}
    if cfg.dense_prefix_layers > 0:
        x, new_cache["prefix_layers"] = _decode_stack(
            prefix_layout(cfg), params["prefix_groups"], cache["prefix_layers"],
            x, pos, prefix_cfg(cfg), memory,
        )
    x, new_cache["layers"] = _decode_stack(
        layout, params["groups"], cache["layers"], x, pos, cfg, memory
    )
    hidden = apply_norm(x, params["norm_f"], cfg.norm, cfg.norm_eps)
    logits = _logits(params, cfg, hidden)
    return logits, new_cache


def pad_cache(cache: dict, cfg: ModelConfig, max_len: int) -> dict:
    """Grow sequence-indexed cache leaves to ``max_len`` decode slots.

    Leaves are stacked per group: self-attention K/V (groups, B, KV, D, S)
    grow on their last axis to ``attn.cache_len`` slots, the MLA latent and
    rope keys (groups, B, S, ...) on axis 2 to ``max_len``. State caches
    (mamba/rwkv) and cross-attention memories are untouched. Ring buffers
    (SWA) are already bounded by the window and never grow.
    """
    seq_axis = {"k": -1, "v": -1, "c_kv": 2, "k_rope": 2}
    target = {"k": attn.cache_len(cfg, max_len), "v": attn.cache_len(cfg, max_len),
              "c_kv": max_len, "k_rope": max_len}

    def walk(tree):
        out = {}
        for k, val in tree.items():
            if isinstance(val, dict):
                val = walk(val)
            elif k in seq_axis and val.shape[seq_axis[k]] < target[k]:
                pad = [(0, 0)] * val.ndim
                pad[seq_axis[k]] = (0, target[k] - val.shape[seq_axis[k]])
                val = jnp.pad(val, pad)
            out[k] = val
        return out

    new = dict(cache)
    new["layers"] = walk(cache["layers"])
    if "prefix_layers" in cache:
        new["prefix_layers"] = walk(cache["prefix_layers"])
    return new


def prefill(params: dict, cfg: ModelConfig, batch: dict, max_len: int | None = None):
    """Full-context forward returning last-position logits + decode cache."""
    logits, aux, cache = forward(params, cfg, batch, return_cache=True)
    if max_len is not None:
        cache = pad_cache(cache, cfg, max_len)
    return logits[:, -1:], cache
