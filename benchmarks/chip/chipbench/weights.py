"""Random weights of a block, made from a seed on the device.

One jitted call makes every tensor of a block's ``layout`` in the type it
is served in (bf16), under flat names, layers stacked on a leading axis.
The harness hands these to the program; the plain reference makes them
again from the same seed with the same call, so it takes nothing the
program has made.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

Layout = dict[str, tuple[tuple[int, ...], str, float]]


@partial(jax.jit, static_argnums=(0, 2))
def _make(items: tuple, key: jax.Array, dtype) -> dict[str, jax.Array]:
    out = {}
    for i, (name, (shape, kind, scale)) in enumerate(items):
        z = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
        if kind == "normal":
            x = z * scale
        elif kind == "scale":
            x = 1.0 + scale * z
        else:
            x = scale * z
        out[name] = x.astype(dtype)
    return out


def make(layout: Layout, seed32: int, dtype=jnp.bfloat16) -> dict[str, jax.Array]:
    """Every weight of ``layout`` (name -> (shape, kind, scale); kind is
    "normal" (scale = std), "scale" (1 + scale * N) or "bias" (scale * N)),
    from a 32-bit seed. The i-th name in sorted order draws from
    ``fold_in(key, i)``."""
    items = tuple(sorted((name, (tuple(shape), kind, float(scale)))
                         for name, (shape, kind, scale) in layout.items()))
    return _make(items, jax.random.key(seed32), jnp.dtype(dtype))
