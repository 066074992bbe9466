"""Random weights of a dense GQA decoder, made from a seed on the device.

One jitted call makes every tensor in the type it is served in (bf16),
in a layout of the benchmark's own: flat names, layers stacked on a
leading axis. The harness hands these to the program; the plain
reference makes them again from the same seed with the same call, so it
takes nothing the program has made.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp


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


def layout(dims: Dims) -> dict[str, tuple[tuple[int, ...], str, float]]:
    """name -> (shape, kind, scale); kind is "normal" (scale = std),
    "scale" (1 + scale * N) or "bias" (scale * N)."""
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


@partial(jax.jit, static_argnums=(0, 2))
def _make(dims: Dims, key: jax.Array, dtype) -> dict[str, jax.Array]:
    out = {}
    for i, (name, (shape, kind, scale)) in enumerate(sorted(layout(dims).items())):
        z = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
        if kind == "normal":
            x = z * scale
        elif kind == "scale":
            x = 1.0 + scale * z
        else:
            x = scale * z
        out[name] = x.astype(dtype)
    return out


def make(dims: Dims, seed32: int, dtype=jnp.bfloat16) -> dict[str, jax.Array]:
    """Every weight of the configuration, from a 32-bit seed."""
    return _make(dims, jax.random.key(seed32), jnp.dtype(dtype))
