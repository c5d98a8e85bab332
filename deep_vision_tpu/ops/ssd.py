"""Chunked state-space scan (the Mamba-2 "SSD" form, Dao & Gu
arXiv:2405.21060 §6) over packed documents.

Per head, with a state ``H`` of ``(head_dim, state)`` that is zero before
each document's first token::

    H_t = exp(dt_t * a) * H_{t-1} + dt_t * x_t (outer) B_t
    y_t = H_t C_t

The recurrence is computed in chunks: inside a chunk the lower-triangular
``(C B^T) * decay`` product, between chunks the carried state.  ``dt``, the
cumulative sums, the decays and the state are float32; the three large
products take their operands in ``x``'s dtype and accumulate in float32.

Documents are contiguous and ``segment_ids`` never decrease along a row
(``data/text.py::pack_documents`` yields them so), hence token ``s`` reaches
token ``t >= s`` exactly where their ids are equal: that one comparison is
the reset, inside a chunk and across chunks alike, wherever the boundary
falls on the chunk grid.  Plain ``jax.numpy``, differentiated by JAX.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def ssd_scan(x, dt, a, b, c, segment_ids, chunk: int):
    """``x`` (B, L, H, P); ``dt`` (B, L, H) float32, positive; ``a`` (H,)
    float32, negative; ``b``, ``c`` (B, L, N), one group shared by the heads;
    ``segment_ids`` (B, L) integers.  Returns ``y`` (B, L, H, P) float32."""
    bsz, length, heads, dim = x.shape
    if length % chunk:
        raise ValueError(f"sequence length {length} is not a multiple of "
                         f"the scan's chunk {chunk}")
    nc = length // chunk
    f32 = jnp.float32
    x = x.reshape(bsz, nc, chunk, heads, dim)
    dt = dt.astype(f32).reshape(bsz, nc, chunk, heads)
    b = b.reshape(bsz, nc, chunk, -1)
    c = c.reshape(bsz, nc, chunk, -1)
    seg = segment_ids.reshape(bsz, nc, chunk)

    # log-decay up to and including each position of its chunk: (B, nc, H, Q)
    cum = jnp.cumsum(jnp.moveaxis(dt * a.astype(f32), 3, 2), axis=-1)
    xdt = x * dt[..., None].astype(x.dtype)

    # inside a chunk: y_i += sum_{j <= i, same document} (C_i . B_j) decay_ij dt_j x_j
    same = seg[:, :, :, None] == seg[:, :, None, :]
    reaches = same & jnp.tril(jnp.ones((chunk, chunk), bool))
    decay = jnp.exp(jnp.where(reaches[:, :, None],
                              cum[..., :, None] - cum[..., None, :], -jnp.inf))
    scores = jnp.einsum("bcin,bcjn->bcij", c, b, preferred_element_type=f32)
    y = jnp.einsum("bchij,bcjhp->bcihp",
                   (scores[:, :, None] * decay).astype(x.dtype), xdt,
                   preferred_element_type=f32)

    # what each chunk leaves in the state at its end: the tokens of the
    # document its last token belongs to, decayed to that token
    last = seg[:, :, -1]
    to_end = jnp.exp(cum[..., -1:] - cum) * (seg == last[:, :, None])[:, :, None]
    left = jnp.einsum("bcjhp,bcjn->bchpn",
                      xdt * jnp.moveaxis(to_end, 2, 3)[..., None].astype(x.dtype),
                      b, preferred_element_type=f32)

    # between chunks: the state at the end of chunk p gathers what chunks
    # q <= p left of the same document, decayed over the chunks between
    total = jnp.cumsum(cum[..., -1], axis=1)                      # (B, nc, H)
    carries = (last[:, :, None] == last[:, None, :]) & jnp.tril(
        jnp.ones((nc, nc), bool))
    across = jnp.exp(jnp.where(carries[..., None],
                               total[:, :, None] - total[:, None, :], -jnp.inf))
    state = jnp.einsum("bpqh,bqhdn->bphdn", across, left,
                       precision=jax.lax.Precision.HIGHEST)
    state = jnp.concatenate([jnp.zeros_like(state[:, :1]), state[:, :-1]], axis=1)

    # the carried state's part of each position: same document as the
    # chunk before ended in, decayed from the chunk's start
    before = jnp.concatenate([last[:, :1], last[:, :-1]], axis=1)
    from_start = jnp.exp(cum) * (seg == before[:, :, None])[:, :, None]
    y = y + jnp.moveaxis(from_start, 2, 3)[..., None] * jnp.einsum(
        "bcin,bchpn->bcihp", c, state.astype(x.dtype),
        preferred_element_type=f32)
    return y.reshape(bsz, length, heads, dim)
