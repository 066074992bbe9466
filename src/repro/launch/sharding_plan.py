"""Sharding plans for the dry-run: state, batch, and cache PartitionSpecs.

Parameters use the logical-axis rules (models/sharding.py). Optimizer
state mirrors parameter specs (AdamW) or drops the factored axis
(Adafactor). Caches get explicit per-leaf specs with divisibility-aware
fallbacks: when KV heads don't divide the model axis (qwen's 8 kv heads
on a 16-wide axis), the cache shards its *sequence* dim instead —
sequence-parallel attention, which GSPMD lowers to partial-softmax
collectives.
"""

from __future__ import annotations

from typing import Any

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..configs.base import ModelConfig, TrainConfig
from ..models.sharding import ParamLeaf, param_pspecs, resolve_axes, rules_for


def _axis_size(mesh: Mesh, axis) -> int:
    if axis is None:
        return 1
    if isinstance(axis, (tuple, list)):
        n = 1
        for a in axis:
            n *= mesh.shape.get(a, 1)
        return n
    return mesh.shape.get(axis, 1)


def batch_axes(mesh: Mesh):
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def _full(pspec: P, ndim: int) -> tuple:
    t = tuple(pspec)
    return t + (None,) * (ndim - len(t))


# ---------------------------------------------------------------------------
# Train-state sharding
# ---------------------------------------------------------------------------


def opt_pspecs(spec_tree: Any, pspecs: Any, tcfg: TrainConfig) -> Any:
    """Optimizer-state PartitionSpec tree mirroring the param tree."""
    is_leaf = lambda x: isinstance(x, ParamLeaf)
    if tcfg.optimizer == "adafactor":
        from ..train.optimizer import _factored

        def per_leaf(leaf: ParamLeaf, ps: P):
            t = _full(ps, len(leaf.shape))
            if _factored(leaf.shape):
                return {"vr": P(*t[:-1]), "vc": P(*(t[:-2] + t[-1:]))}
            return {"v": P(*t)}

        return {"v": jax.tree.map(per_leaf, spec_tree, pspecs, is_leaf=is_leaf)}
    return {
        "m": jax.tree.map(lambda _leaf, ps: ps, spec_tree, pspecs, is_leaf=is_leaf),
        "v": jax.tree.map(lambda _leaf, ps: ps, spec_tree, pspecs, is_leaf=is_leaf),
    }


def state_pspecs(cfg: ModelConfig, spec_tree: Any, mesh: Mesh, tcfg: TrainConfig) -> dict:
    rules = rules_for(cfg)
    pps = param_pspecs(spec_tree, rules, mesh)
    return {
        "step": P(),
        "params": pps,
        "opt": opt_pspecs(spec_tree, pps, tcfg),
    }


def batch_pspecs(cfg: ModelConfig, batch_tree: Any, mesh: Mesh, batch_dim: int = 0) -> Any:
    """batch_dim=1 for pre-split microbatch leaves shaped (k, B/k, ...)."""
    baxes = batch_axes(mesh)

    def per_leaf(leaf):
        if (
            baxes
            and len(leaf.shape) > batch_dim
            and leaf.shape[batch_dim] % max(_axis_size(mesh, baxes), 1) == 0
        ):
            return P(*([None] * batch_dim + [baxes]))
        return P()

    return jax.tree.map(per_leaf, batch_tree)


def microbatch_specs(batch_tree: Any, k: int) -> Any:
    """Reshape abstract batch leaves (B, ...) -> (k, B/k, ...)."""
    import jax as _jax

    def per_leaf(leaf):
        b = leaf.shape[0]
        assert b % k == 0, f"batch {b} not divisible by microbatches {k}"
        return _jax.ShapeDtypeStruct((k, b // k) + tuple(leaf.shape[1:]), leaf.dtype)

    return jax.tree.map(per_leaf, batch_tree)


# ---------------------------------------------------------------------------
# Decode-cache sharding
# ---------------------------------------------------------------------------


def cache_pspecs(cfg: ModelConfig, cache_tree: Any, mesh: Mesh) -> Any:
    """Walk the cache dict; assign specs by leaf name + divisibility."""
    baxes = batch_axes(mesh)
    model_n = mesh.shape.get("model", 1)
    batch_n = _axis_size(mesh, baxes)

    def bspec(b: int):
        return baxes if (baxes and b % batch_n == 0) else None

    def leaf_spec(name: str, s) -> P:
        shp = s.shape
        if name in ("k", "v"):  # (L, B, KV, HD, S): positions minor
            _, b, kv, hd, seq = shp
            if kv % model_n == 0:
                return P(None, bspec(b), "model", None, None)
            if seq % model_n == 0:  # sequence-parallel KV cache
                return P(None, bspec(b), None, None, "model")
            return P(None, bspec(b))
        if name in ("xk", "xv"):  # (L, B, M, KV, HD)
            _, b, m, kv, hd = shp
            if kv % model_n == 0:
                return P(None, bspec(b), None, "model", None)
            return P(None, bspec(b))
        if name == "c_kv":  # (L, B, S, R) — MLA latent: shard seq (TP on q side)
            _, b, seq, r = shp
            if seq % model_n == 0:
                return P(None, bspec(b), "model", None)
            return P(None, bspec(b))
        if name == "k_rope":  # (L, B, S, dr) — shared across heads; align with c_kv
            _, b, seq, dr = shp
            if seq % model_n == 0:
                return P(None, bspec(b), "model", None)
            return P(None, bspec(b))
        if name == "h":  # mamba state (L, B, DI, N)
            _, b, di, n = shp
            if di % model_n == 0:
                return P(None, bspec(b), "model", None)
            return P(None, bspec(b))
        if name == "conv":  # (L, B, CW-1, DI)
            _, b, cw, di = shp
            if di % model_n == 0:
                return P(None, bspec(b), None, "model")
            return P(None, bspec(b))
        if name == "wkv":  # rwkv state (L, B, H, K, V)
            _, b, h, *_ = shp
            if h % model_n == 0:
                return P(None, bspec(b), "model", None, None)
            return P(None, bspec(b))
        if name == "x_prev":  # (L, B, D)
            _, b, d = shp
            return P(None, bspec(b), "model" if d % model_n == 0 else None)
        return P()  # replicate small/unknown leaves

    def walk(tree):
        if isinstance(tree, dict):
            return {k: (walk(v) if isinstance(v, dict) else leaf_spec(k, v)) for k, v in tree.items()}
        return tree

    out = {"layers": walk(cache_tree["layers"])}
    if "prefix_layers" in cache_tree:
        out["prefix_layers"] = walk(cache_tree["prefix_layers"])
    mem = cache_tree.get("memory")
    if mem is not None:
        b, m, d = mem.shape
        out["memory"] = P(bspec(b))
    else:
        out["memory"] = None
    return out


def decode_in_pspecs(cfg: ModelConfig, specs: dict, mesh: Mesh) -> dict:
    baxes = batch_axes(mesh)
    b = specs["tokens"].shape[0]
    batch_n = _axis_size(mesh, baxes)
    tokens_spec = P(baxes) if (baxes and b % batch_n == 0) else P()
    return {
        "tokens": tokens_spec,
        "cache": cache_pspecs(cfg, specs["cache"], mesh),
        "pos": P(),
    }


def to_shardings(pspec_tree: Any, mesh: Mesh) -> Any:
    return jax.tree.map(
        lambda ps: NamedSharding(mesh, ps) if ps is not None else None,
        pspec_tree,
        is_leaf=lambda x: isinstance(x, P),
    )
