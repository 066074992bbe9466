"""Decode attention Pallas TPU kernel over the stacked, position-minor KV cache.

One new query token per batch row attends to the cache where it lies: the
cache is ``(layers, B, KV, D, S)`` with head_dim on sublanes and positions on
lanes, the layout the decode step keeps and prefill writes, ``S`` a multiple
of 128. The layer is a scalar-prefetch argument, so the kernel reads one
layer's blocks straight out of the whole stack: no per-layer slice, no
relayout, no padding of batch or head_dim into matmul tiles.

The kernel also writes the new token's K and V into slot ``pos`` of the
layer: the cache is aliased to two outputs whose only block is the 128-slot
tile holding ``pos``, so each step writes that tile once per (batch row,
block of heads) and leaves the rest of the cache where it lies.

Per (batch row, block of kv heads) the grid walks position blocks. All heads
of the block go through the MXU together: scores are ``q @ K_blk`` batched
over heads (f32 accumulation), the softmax runs online in f32 over the
blocks up to ``pos``, and ``p @ V_blkᵀ`` accumulates in f32. Query heads of
one GQA group ride as rows of ``q``, padded to a sublane tile of 8. Blocks
past ``pos`` are neither fetched (the index map clamps to the block holding
``pos``) nor computed.

Block sizes adapt to the shapes: whole positions and as many heads as fit
``block_bytes`` when one head's K is small (stablelm: 80 x 384), else one
head and the largest 128-multiple of positions that divides the cache.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANES = 128  # slots per lane tile; the cache holds whole tiles
BLOCK_BYTES = 1 << 20  # K bytes per grid step; V adds as many


def block_shape(kv: int, d: int, s: int, itemsize: int,
                block_bytes: int = BLOCK_BYTES) -> tuple[int, int]:
    """(kv heads, positions) read per grid step."""
    per_head = d * s * itemsize
    if per_head <= block_bytes:
        heads = max(h for h in range(1, kv + 1) if kv % h == 0 and h * per_head <= block_bytes)
        return heads, s
    fits = [c for c in range(LANES, s + 1, LANES)
            if s % c == 0 and d * c * itemsize <= block_bytes]
    return 1, max(fits, default=LANES)


def _decode_kernel(layer_ref, pos_ref, q_ref, kn_ref, vn_ref, k_ref, v_ref,
                   o_ref, ko_ref, vo_ref, m_scr, l_scr, acc_scr,
                   *, block_s: int, scale: float):
    del layer_ref  # read by the index maps only
    j = pl.program_id(2)
    pos = pos_ref[0]
    at = pos - j * block_s  # pos within this block

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # The block holding pos: put the new column into its lane tile, in this
    # block's buffer (for the scores below) and in the aliased outputs.
    for c in range(block_s // LANES):
        @pl.when((at >= c * LANES) & (at < (c + 1) * LANES))
        def _insert(c=c):
            tile = pl.ds(c * LANES, LANES)
            lane = jax.lax.broadcasted_iota(jnp.int32, ko_ref.shape, 2)
            for new_ref, ref, out_ref in ((kn_ref, k_ref, ko_ref), (vn_ref, v_ref, vo_ref)):
                merged = jnp.where(lane == at - c * LANES, new_ref[...], ref[:, :, tile])
                ref[:, :, tile] = merged
                out_ref[...] = merged

    @pl.when(at >= 0)
    def _step():
        # Every head of the block at once: (heads, rows, D) @ (heads, D, block_s).
        k, v = k_ref[...], v_ref[...]
        s = jax.lax.dot_general(q_ref[...], k, (((2,), (1,)), ((0,), (0,))),
                                preferred_element_type=jnp.float32)
        col = jax.lax.broadcasted_iota(jnp.int32, (1,) + s.shape[1:], 2)
        s = jnp.where(col <= at, s * scale, NEG_INF)  # (heads, rows, block_s)
        m_prev = m_scr[...]  # (heads, rows, 128), lane-replicated
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=2, keepdims=True))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur[..., :1])
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=2, keepdims=True)
        pv = jax.lax.dot_general(p.astype(v.dtype), v, (((2,), (2,)), ((0,), (0,))),
                                 preferred_element_type=jnp.float32)  # (heads, rows, D)
        acc_scr[...] = acc_scr[...] * alpha[..., :1] + pv
        m_scr[...] = m_cur

    @pl.when(j == pl.num_programs(2) - 1)
    def _finalize():
        o_ref[...] = (acc_scr[...] / l_scr[...][..., :1]).astype(o_ref.dtype)


def decode_attention_stacked(
    q: jnp.ndarray,  # (B, KV, G, D): the group's query heads as rows
    k_new: jnp.ndarray,  # (B, KV, D): this token's K, for slot pos
    v_new: jnp.ndarray,  # (B, KV, D)
    k: jnp.ndarray,  # (L, B, KV, D, S)
    v: jnp.ndarray,  # (L, B, KV, D, S)
    layer: jnp.ndarray,  # int32 scalar
    pos: jnp.ndarray,  # int32 scalar: slots 0..pos are valid, pos < S
    *,
    block_bytes: int = BLOCK_BYTES,
    interpret: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Write the new K/V into slot ``pos`` of layer ``layer`` and attend one
    token per row over that layer. Returns the attention (B, KV, G, D) in
    q's dtype and the cache, updated in place where it is donated."""
    b, kv, g, d = q.shape
    s = k.shape[-1]
    if s % LANES:
        raise ValueError(f"the cache holds {s} slots, not whole {LANES}-slot tiles")
    heads, block_s = block_shape(kv, d, s, k.dtype.itemsize, block_bytes)
    rows = -(-g // 8) * 8  # a whole sublane tile of query rows
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, rows - g), (0, 0))).astype(k.dtype)

    def q_map(i, h, j, layer_ref, pos_ref):
        return i, h, 0, 0

    def kv_map(i, h, j, layer_ref, pos_ref):
        return layer_ref[0], i, h, 0, jnp.minimum(j, pos_ref[0] // block_s)

    def tile_map(i, h, j, layer_ref, pos_ref):
        return layer_ref[0], i, h, 0, pos_ref[0] // LANES

    kv_spec = pl.BlockSpec((None, None, heads, d, block_s), kv_map)
    tile_spec = pl.BlockSpec((None, None, heads, d, LANES), tile_map)
    new_spec = pl.BlockSpec((None, heads, d, 1), q_map)
    out, k, v = pl.pallas_call(
        functools.partial(_decode_kernel, block_s=block_s, scale=d ** -0.5),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, kv // heads, s // block_s),
            in_specs=[pl.BlockSpec((None, heads, rows, d), q_map), new_spec, new_spec,
                      kv_spec, kv_spec],
            out_specs=[pl.BlockSpec((None, heads, rows, d), q_map), tile_spec, tile_spec],
            scratch_shapes=[
                pltpu.VMEM((heads, rows, 128), jnp.float32),  # running max
                pltpu.VMEM((heads, rows, 128), jnp.float32),  # running denominator
                pltpu.VMEM((heads, rows, d), jnp.float32),  # output accumulator
            ],
        ),
        out_shape=[jax.ShapeDtypeStruct((b, kv, rows, d), q.dtype),
                   jax.ShapeDtypeStruct(k.shape, k.dtype), jax.ShapeDtypeStruct(v.shape, v.dtype)],
        input_output_aliases={5: 1, 6: 2},  # operands count the two scalars
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), jnp.reshape(pos, (1,)).astype(jnp.int32),
      qp, k_new[..., None].astype(k.dtype), v_new[..., None].astype(v.dtype), k, v)
    return out[:, :, :g], k, v
