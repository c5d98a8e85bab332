"""Causal grouped-query attention over packed documents, by blocks.

Queries are taken a block of rows at a time; each block walks the blocks
of keys at or before it with a running maximum and a running sum (the
online softmax of Milakov & Gimelshein arXiv:1805.02867, as flash attention
uses it), so no ``(heads, L, L)`` tensor of the whole sequence is ever held.
Each block of queries is rematerialised in the backward pass: what autodiff
keeps between the passes is the block's inputs, not its probabilities.

A key is visible to a query where it is not later in the row and carries
the same segment id (another document's keys are masked).  No position term
of any kind is applied here.  Scores and the softmax are float32; the two
products take their operands in the inputs' dtype.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

_MASKED = -1e30  # finite: a row whose keys are all masked so far stays finite


def _attend_block(q, keys, values, seg_q, seg_k, start: int, scale: float,
                  block: int):
    """One block of queries ``q`` (B, Q, Hkv, G, D) starting at row ``start``
    against ``keys``/``values`` (B, n*block, Hkv, D), the rows up to the end
    of that block."""
    bsz, rows, kv_heads, group, dim = q.shape
    q_pos = start + jnp.arange(rows)
    shape = (bsz, kv_heads, group, rows)
    top = jnp.full(shape, _MASKED, jnp.float32)
    total = jnp.zeros(shape, jnp.float32)
    acc = jnp.zeros(shape + (dim,), jnp.float32)
    # a Python loop, not lax.scan: the blocks are few and every slice static
    for first in range(0, keys.shape[1], block):
        k, v = keys[:, first:first + block], values[:, first:first + block]
        s = jnp.einsum("bqhgd,bkhd->bhgqk", q, k,
                       preferred_element_type=jnp.float32) * scale
        visible = ((q_pos[:, None] >= first + jnp.arange(block)[None, :])[None]
                   & (seg_q[:, :, None] == seg_k[:, None, first:first + block])
                   )[:, None, None]
        s = jnp.where(visible, s, _MASKED)
        new_top = jax.lax.stop_gradient(jnp.maximum(top, s.max(-1)))
        p = jnp.where(visible, jnp.exp(s - new_top[..., None]), 0.0)
        shrink = jnp.exp(top - new_top)
        total = shrink * total + p.sum(-1)
        acc = shrink[..., None] * acc + jnp.einsum(
            "bhgqk,bkhd->bhgqd", p.astype(v.dtype), v,
            preferred_element_type=jnp.float32)
        top = new_top
    out = acc / total[..., None]       # every row sees at least itself
    return jnp.moveaxis(out, 3, 1).reshape(bsz, rows, kv_heads * group, dim)


def causal_attention(q, k, v, segment_ids, scale: float, block: int = 512):
    """``q`` (B, L, Hq, D); ``k``, ``v`` (B, L, Hkv, D) with Hq a multiple of
    Hkv; ``segment_ids`` (B, L).  Returns (B, L, Hq, D) in ``q``'s dtype."""
    bsz, length, heads, dim = q.shape
    kv_heads = k.shape[2]
    if heads % kv_heads:
        raise ValueError(f"{heads} query heads over {kv_heads} key heads")
    block = min(block, length)
    if length % block:
        raise ValueError(f"sequence length {length} is not a multiple of "
                         f"the attention block {block}")
    q = q.reshape(bsz, length, kv_heads, heads // kv_heads, dim)
    attend = jax.checkpoint(_attend_block, static_argnums=(5, 6, 7))
    out = [attend(q[:, lo:lo + block], k[:, :lo + block], v[:, :lo + block],
                  segment_ids[:, lo:lo + block], segment_ids[:, :lo + block],
                  lo, scale, block)
           for lo in range(0, length, block)]
    return jnp.concatenate(out, axis=1).astype(v.dtype)
