"""The dense GQA SwiGLU decoder block: its sizes, weights, plain
reference and work counts (the contract is in ``chipbench/blocks.py``).

The reference follows the architecture as the repository declares it
(the configuration files list where that departs from the published
model): pre-norm blocks; LayerNorm or RMSNorm; rotary embedding over the
whole head in rotate-half form; causal softmax attention scaled by
head_dim ** -0.5, query heads grouped over the key/value heads; a SwiGLU
MLP, silu(x W_gate) * (x W_up) W_down; a final norm and an output head
(the embedding's transpose when tied). It runs one layer per call.

The counts are of the work the algorithm needs, whatever implements it:
a decode step reads the parameters and the keys and values of the
positions before it, not the empty slots of a cache that is longer; a
prefill computes the logits of the last position only. An
implementation that does less than the program does today reads as a
higher share of the roofline, never above 100%.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp

from chipbench import system
from chipbench.reference import _attention, _embed, _mm, _norm, _rope

BYTES = 2  # bf16, the type the configurations serve in


@dataclass(frozen=True)
class Dims:
    """The shapes of one configuration, read from its file."""

    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    tied: bool
    norm: str  # layernorm | rmsnorm
    norm_eps: float
    rope_theta: float

    @classmethod
    def from_config(cls, cfg: dict) -> "Dims":
        """From a configuration file's published keys, as run. The norm is
        LayerNorm where the file has ``layer_norm_eps``, else RMSNorm."""
        d, h = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
        layernorm = "layer_norm_eps" in cfg
        return cls(
            layers=int(cfg["num_hidden_layers"]), d_model=d, heads=h,
            kv_heads=int(cfg["num_key_value_heads"]),
            head_dim=int(cfg.get("head_dim", d // h)), d_ff=int(cfg["intermediate_size"]),
            vocab=int(cfg["vocab_size"]), tied=bool(cfg["tie_word_embeddings"]),
            norm="layernorm" if layernorm else "rmsnorm",
            norm_eps=float(cfg["layer_norm_eps" if layernorm else "rms_norm_eps"]),
            rope_theta=float(cfg["rope_theta"]))


def dims_of(mc) -> Dims:
    if mc.family != "dense" or mc.attention != "gqa" or mc.activation != "swiglu":
        raise ValueError(f"{mc.name}: not a dense GQA SwiGLU model")
    if mc.qkv_bias or not mc.use_rope or mc.sliding_window:
        raise ValueError(f"{mc.name}: qkv bias, no rotary or a window is not modelled")
    return Dims(layers=mc.num_layers, d_model=mc.d_model, heads=mc.num_heads,
                kv_heads=mc.num_kv_heads, head_dim=mc.head_dim, d_ff=mc.d_ff,
                vocab=mc.vocab_size, tied=mc.tied_embeddings, norm=mc.norm,
                norm_eps=mc.norm_eps, rope_theta=mc.rope_theta)


# ----------------------------------------------------------------- weights

def layout(dims: Dims) -> dict[str, tuple[tuple[int, ...], str, float]]:
    d, L, h, kv, hd, f, v = (dims.d_model, dims.layers, dims.heads, dims.kv_heads,
                             dims.head_dim, dims.d_ff, dims.vocab)
    out: dict[str, tuple[tuple[int, ...], str, float]] = {
        "embed": ((v, d), "normal", d ** -0.5)}
    if not dims.tied:
        out["head"] = ((d, v), "normal", d ** -0.5)
    norms = ["norm1", "norm2"]
    for n in norms:
        out[f"layers.{n}.scale"] = ((L, d), "scale", 0.1)
        if dims.norm == "layernorm":
            out[f"layers.{n}.bias"] = ((L, d), "bias", 0.1)
    out.update({
        "layers.wq": ((L, d, h, hd), "normal", d ** -0.5),
        "layers.wk": ((L, d, kv, hd), "normal", d ** -0.5),
        "layers.wv": ((L, d, kv, hd), "normal", d ** -0.5),
        "layers.wo": ((L, h, hd, d), "normal", (h * hd) ** -0.5),
        "layers.w_gate": ((L, d, f), "normal", d ** -0.5),
        "layers.w_up": ((L, d, f), "normal", d ** -0.5),
        "layers.w_down": ((L, f, d), "normal", f ** -0.5),
        "norm_f.scale": ((d,), "scale", 0.1),
    })
    if dims.norm == "layernorm":
        out["norm_f.bias"] = ((d,), "bias", 0.1)
    return out


def program_params(w: dict, dims: Dims, mc) -> dict:
    """The benchmark's weights in the program's parameter tree (the same
    buffers, no copy)."""
    def norm(prefix: str) -> dict:
        out = {"scale": w[f"{prefix}.scale"]}
        if dims.norm == "layernorm":
            out["bias"] = w[f"{prefix}.bias"]
        return out

    block = {
        "norm1": norm("layers.norm1"), "norm2": norm("layers.norm2"),
        "mixer": {k: w[f"layers.{k}"] for k in ("wq", "wk", "wv", "wo")},
        "mlp": {"w_gate": w["layers.w_gate"], "w_in": w["layers.w_up"],
                "w_out": w["layers.w_down"]},
    }
    params = {"embed": w["embed"], "groups": {"b0": block}, "norm_f": norm("norm_f")}
    if not dims.tied:
        params["lm_head"] = w["head"]
    return system.checked(params, mc)


# --------------------------------------------------------------- reference

@partial(jax.jit, static_argnums=(2, 3))
def _block(w: dict, x: jax.Array, dims: Dims, quant: bool) -> jax.Array:
    w = {k: a.astype(jnp.float32) for k, a in w.items()}
    h = _norm(x, w["norm1.scale"], w.get("norm1.bias"), dims.norm, dims.norm_eps)
    q = _rope(_mm("btd,dhk->bthk", h, w["wq"], quant), dims.rope_theta)
    k = _rope(_mm("btd,dhk->bthk", h, w["wk"], quant), dims.rope_theta)
    v = _mm("btd,dhk->bthk", h, w["wv"], quant)
    x = x + _mm("bthk,hkd->btd", _attention(q, k, v, quant), w["wo"], quant)
    h = _norm(x, w["norm2.scale"], w.get("norm2.bias"), dims.norm, dims.norm_eps)
    gate = _mm("btd,df->btf", h, w["w_gate"], quant)
    up = _mm("btd,df->btf", h, w["w_up"], quant)
    return x + _mm("btf,fd->btd", jax.nn.silu(gate) * up, w["w_down"], quant)


@partial(jax.jit, static_argnums=(3, 4, 5))
def _head(w: dict, x: jax.Array, embed: jax.Array, start: int, dims: Dims, quant: bool):
    h = _norm(x[:, start:], w["norm_f.scale"].astype(jnp.float32),
              w["norm_f.bias"].astype(jnp.float32) if "norm_f.bias" in w else None,
              dims.norm, dims.norm_eps)
    head = embed.T if dims.tied else w["head"]
    return _mm("btd,dv->btv", h, head.astype(jnp.float32), quant)


def logits(w: dict, dims: Dims, tokens: jax.Array, start: int, quant: bool = False) -> jax.Array:
    """Logits (B, T - start, V) of positions start..T-1 of ``tokens`` (B, T)."""
    x = _embed(w["embed"], tokens)
    for layer in range(dims.layers):
        lw = {k.removeprefix("layers."): a[layer] for k, a in w.items() if k.startswith("layers.")}
        x = _block(lw, x, dims, quant)
    tail = {k: a for k, a in w.items() if k.startswith(("norm_f.", "head"))}
    return _head(tail, x, w["embed"], start, dims, quant)


# ------------------------------------------------------------------ counts

def layer_matrix_params(d: Dims) -> int:
    attn = d.d_model * d.head_dim * (2 * d.heads + 2 * d.kv_heads)  # wq, wo, wk, wv
    return attn + 3 * d.d_model * d.d_ff  # w_gate, w_up, w_down


def param_count(d: Dims) -> int:
    norms = (2 if d.norm == "layernorm" else 1) * d.d_model
    per_layer = layer_matrix_params(d) + 2 * norms
    head = 0 if d.tied else d.d_model * d.vocab
    return d.layers * per_layer + d.vocab * d.d_model + head + norms


def _kv_bytes(d: Dims, batch: int, positions: int) -> int:
    return 2 * d.layers * batch * positions * d.kv_heads * d.head_dim * BYTES


def prefill_flops(d: Dims, batch: int, seq: int) -> float:
    matmul = 2 * batch * seq * d.layers * layer_matrix_params(d)
    attn = d.layers * 4 * batch * d.heads * d.head_dim * seq * (seq + 1) / 2  # causal
    return matmul + attn + 2 * batch * d.d_model * d.vocab  # last position's logits


def prefill_bytes(d: Dims, batch: int, seq: int) -> float:
    return param_count(d) * BYTES + _kv_bytes(d, batch, seq) + batch * seq * d.d_model * BYTES


def decode_flops(d: Dims, batch: int, pos: int) -> float:
    matmul = 2 * batch * d.layers * layer_matrix_params(d)
    attn = d.layers * 4 * batch * d.heads * d.head_dim * (pos + 1)  # keys 0..pos
    return matmul + attn + 2 * batch * d.d_model * d.vocab


def decode_bytes(d: Dims, batch: int, pos: int) -> float:
    """Parameters, the K and V of positions before ``pos``, the new K and V."""
    return (param_count(d) * BYTES + _kv_bytes(d, batch, pos) + _kv_bytes(d, batch, 1)
            + batch * d.d_model * BYTES)


def flops(d: Dims, kind: str, batch: int, n: int) -> float:
    return prefill_flops(d, batch, n) if kind == "prefill" else decode_flops(d, batch, n)


def nbytes(d: Dims, kind: str, batch: int, n: int) -> float:
    return prefill_bytes(d, batch, n) if kind == "prefill" else decode_bytes(d, batch, n)
