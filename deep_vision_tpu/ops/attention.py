"""Causal grouped-query attention over packed documents: a forward and a
backward Pallas kernel under one ``jax.custom_vjp``, compiled by Mosaic on a
TPU and interpreted elsewhere.

A key is visible to a query where it is not later in the row and carries
the same segment id (another document's keys are masked).  No position term
of any kind is applied here.  Scores, mask, softmax and accumulators are
float32; the two products take their operands in the inputs' dtype and
accumulate in float32 (float32 operands are multiplied in full float32), the
probabilities cast to ``v``'s dtype before the second one.

Queries are taken a block of rows at a time; each block walks the blocks of
keys that hold a row at or before its last one, in order, with a running
maximum and a running sum (the online softmax of Milakov & Gimelshein
arXiv:1805.02867, as flash attention uses it, Dao et al. arXiv:2205.14135).
Key blocks wholly above the diagonal are neither read nor computed, and
neither are the key blocks of earlier documents: a small int32 table, made
from the segment ids outside the kernels and handed to both by scalar
prefetch, gives each block of queries its first block of keys, the first
whose largest id reaches the queries' smallest; a grid step stands for the
block that many after it, and the steps past the diagonal's block do
nothing.  No key of a block before the first shares an id with a query of
the block, whatever the ids' order, so such a block would come out all
masked, and a masked score contributes exactly nothing: in the forward
kernel whatever a row gathered over masked keys is multiplied by
``exp(-1e30 - m) = 0`` at its first visible key, in the backward kernel a
masked probability is ``exp(-1e30 - lse) = 0`` and every term it enters is a
zero added to a sum.  Leaving those blocks out therefore changes no bit of
the output or of a cotangent; rows of one document visit every block at or
below the diagonal, as they have to (``visited_blocks`` counts both).  A
block's scores, its mask, the maximum, the sum and the probabilities live in
VMEM and nowhere else: HBM sees ``q``, ``k``, ``v``, the output and one
float32 log-sum-exp a query row and head, and in the backward pass ``do``,
``delta = rowsum(do * out)`` and the three cotangents.  The backward kernel
computes a block's probabilities again from the log-sum-exp (no second
online softmax) and from them ``dv``, ``dp``, ``ds``, ``dq`` and ``dk`` in
one visit: ``dq`` is carried in VMEM over a query block's keys, ``dk`` and
``dv`` are float32 outputs that stay in VMEM for the whole row of a grid
step's key heads and are summed over the query heads that share them.

Layout inside the kernels.  A grid step takes the key/value heads that fill
one 128-lane tile (two heads of 64; one head where a head is a tile wide or
wider, as a latent-attention model's 256, and then no lanes are zeroed and
a product runs over the head's whole width) and the query heads that share
them;
``k`` and ``v`` are read once for all of those and never repeated in HBM.
Every block of scores is held transposed, keys down the sublanes and queries
along the lanes, so that the softmax's maximum and sum run down the sublanes
(plain vector operations), the running values and the log-sum-exp are
lane-dense rows, and every product is a plain or an ``A B^T`` one with no
transpose in the kernel: XLA hands ``v`` over transposed in the forward pass
and ``k`` in both orientations in the backward pass, and takes the output and
``dq`` back transposed, in the same passes that gather the heads of a tile.
A head's scores come from a product over the tile's whole width with the
other head's lanes of ``k`` zeroed, which costs the matrix unit what a
64-wide product would.

The masked score is a finite ``-1e30``.  A row whose keys so far are all
masked carries a maximum of ``-1e30`` and a sum of ones that mean nothing;
the first visible key's maximum shrinks both by ``exp(-1e30 - m) = 0``, and
the last block a row visits always holds the row itself.

``out`` and the log-sum-exp carry the names ``OUT`` and ``LSE`` inside the
forward rule, so that a rematerialised layer whose policy keeps both does
not run the forward kernel a second time.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deep_vision_tpu.ops.ssd import F32, LANE, NT, _dot, _interpret, _lanes_of

_MASKED = -1e30  # finite: a row whose keys are all masked so far stays finite
#: names of what the backward pass reads beside ``q``, ``k``, ``v``
OUT, LSE = "attention_out", "attention_lse"
#: the largest block of queries (lanes of a face) and of keys (sublanes).
#: On the chip (PERF.md s6: PR 34 at 32 / 8 heads of 64, PR 35 at 20 / 20
#: heads of 256, rows of 8,192, forward + backward with the layout passes)
#: 512 x 512 read 12.8 and 19.9 ms a layer; at heads of 256, 256 x 512 read
#: 22.8, 512 x 256 22.0, 256 x 256 27.3, and 1,024 x 512 19.1.  With the
#: earlier documents' blocks skipped (PR 36, the cells' packed rows, 42% of
#: the 512 x 512 causal pairs visited): 6.0 and 11.6 ms at 512 x 512; at heads
#: of 256 256 x 512 14.0, 512 x 256 13.5, 256 x 256 17.7, 1,024 x 512 11.5; at
#: heads of 64 256 x 256 9.3 and 1,024 x 512 6.6
MAX_BLOCK_Q, MAX_BLOCK_K = 512, 512
MIB = 2 ** 20


def _heads_per_tile(kv_heads: int, dim: int) -> int:
    """Key/value heads whose lanes make up one tile: as many as fit 128
    lanes and divide the heads."""
    per_tile = max(1, min(kv_heads, LANE // dim))
    while kv_heads % per_tile:
        per_tile -= 1
    return per_tile


def _blocks(length: int, block: int) -> tuple[int, int]:
    """Rows of queries and of keys a grid step takes: ``block`` (an upper
    bound, and a divisor of the row) halved down to the kernels' own."""
    block = min(block, length)
    if length % block:
        raise ValueError(f"sequence length {length} is not a multiple of "
                         f"the attention block {block}")

    def under(limit):
        rows = block
        while rows > limit and rows % 2 == 0:
            rows //= 2
        return rows

    return under(MAX_BLOCK_Q), under(MAX_BLOCK_K)


def _last_block(qi, block_q: int, block_k: int):
    """The last block of keys that holds a row of query block ``qi``."""
    return ((qi + 1) * block_q - 1) // block_k


def vmem_bytes(length: int, group: int, per_tile: int, dim: int,
               block_q: int, block_k: int, itemsize: int) -> dict[str, int]:
    """An upper bound on what each kernel holds in VMEM at these shapes:
    every block of an argument or a result twice (the pipeline's two
    buffers), the scratch once, and the float32 faces a step has live at
    once (the mask's bias, scores, probabilities and their cast; in the
    backward kernel ``dp`` and ``ds`` too, and the step's ``dk`` and ``dv``)."""
    width, heads = per_tile * dim, per_tile * group
    face = block_q * block_k * 4
    queries, keys = group * block_q * width, block_k * width
    row = 8 * block_q * 4          # a (1, block_q) float32 row pads to 8
    segments = row + block_k * LANE * 4   # the ids as a row and as a column
    fwd = (2 * (itemsize * 2 * (queries + keys) + heads * row + segments)
           + 4 * queries + 2 * heads * row + 4 * face)
    bwd = (2 * (itemsize * 3 * (queries + keys) + 2 * heads * row + segments
                + 2 * 4 * length * width)
           + 4 * queries + 2 * 4 * keys + 6 * face)
    return {"causal_gqa_fwd": fwd, "causal_gqa_bwd": bwd}


def vmem_limit(held: int) -> int:
    """The VMEM a kernel asks for: what it holds by ``vmem_bytes``, rounded
    up to 4 MiB, and Mosaic's default of 16 MiB where that is enough."""
    return max(16 * MIB, -(-held // (4 * MIB)) * 4 * MIB)


def _first_blocks(seg, block_q: int, block_k: int):
    """(B, L) ids -> (B, L / block_q) int32: for each block of queries the
    first block of keys whose largest id reaches the queries' smallest.  No
    key of an earlier block shares an id with any query of the block, whatever
    the ids' order; the block that holds the queries' last row always reaches
    it.  Array methods only, so that the kernels' caller (traced) and
    ``visited_blocks`` (NumPy, on the host) read one definition."""
    bsz, length = seg.shape
    low = seg.reshape(bsz, length // block_q, block_q).min(axis=-1)
    high = seg.reshape(bsz, length // block_k, block_k).max(axis=-1)
    return (high[:, None, :] >= low[:, :, None]).argmax(axis=-1).astype("int32")


def visited_blocks(segment_ids, block: int = 512) -> tuple[int, int]:
    """Pairs of (block of queries, block of keys) the two kernels compute for
    these rows at ``block``, summed over the rows, and the pairs at or below
    the diagonal, which is what rows of one document each would visit.  A
    count a tile of key heads; on the host."""
    seg = np.asarray(segment_ids)
    block_q, block_k = _blocks(seg.shape[1], block)
    last = _last_block(np.arange(seg.shape[1] // block_q), block_q, block_k)
    first = _first_blocks(seg, block_q, block_k)
    return int((last - first + 1).sum()), int(seg.shape[0] * (last + 1).sum())


def _bias(segq_ref, segk_ref, qi, kb, causal: bool):
    """0 where the key (sublane) is visible to the query (lane), ``_MASKED``
    elsewhere: the same document and, in a block that crosses the diagonal,
    not later in the row."""
    visible = segk_ref[0] == segq_ref[0]
    if causal:
        block_k, block_q = visible.shape
        key = kb * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_k, 1), 0)
        query = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (1, block_q), 1)
        visible &= query >= key
    return jnp.where(visible, 0.0, _MASKED)


def _only_head(tile, n: int, per_tile: int, dim: int):
    """``tile`` (rows, per_tile * dim) with the lanes of every head but its
    ``n``-th zeroed."""
    if per_tile == 1:
        return tile
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, tile.shape[1]), 1)
    return jnp.where(_lanes_of(lane, n, dim), tile, jnp.zeros_like(tile))


def _visited(qi, kb, block_q: int, block_k: int, body):
    """Run ``body(causal)`` where the block of keys ``kb`` holds a row the
    block of queries sees; the comparison of positions only where it crosses
    the diagonal."""
    crosses = (kb + 1) * block_k - 1 > qi * block_q

    @pl.when((kb <= _last_block(qi, block_q, block_k)) & crosses)
    def _():
        body(True)

    @pl.when(jnp.logical_not(crosses))
    def _():
        body(False)


def _fwd_kernel(first_ref, q_ref, k_ref, vt_ref, segq_ref, segk_ref, o_ref,
                lse_ref, top, total, acc, *, scale: float, dim: int,
                per_tile: int):
    # dvtlint: traced
    qi, ki = pl.program_id(2), pl.program_id(3)
    kb = first_ref[pl.program_id(0), qi] + ki   # the step's block of keys
    group, block_q = q_ref.shape[2], q_ref.shape[3]
    block_k, dtype = k_ref.shape[2], vt_ref.dtype

    @pl.when(ki == 0)
    def _():
        top[...] = jnp.full(top.shape, _MASKED, F32)
        total[...] = jnp.zeros(total.shape, F32)
        acc[...] = jnp.zeros(acc.shape, F32)

    def attend(causal):
        bias = _bias(segq_ref, segk_ref, qi, kb, causal)
        keys = [_only_head(k_ref[0, 0], n, per_tile, dim) for n in range(per_tile)]

        def head(g, carry):
            q = q_ref[0, 0, g]
            for n in range(per_tile):
                r, rows = n * group + g, slice(n * dim, (n + 1) * dim)
                s = _dot(keys[n], q, NT) * scale + bias
                old = top[r]
                new = jnp.maximum(old, jnp.max(s, axis=0, keepdims=True))
                p = jnp.exp(s - new)
                shrink = jnp.exp(old - new)
                total[r] = shrink * total[r] + jnp.sum(p, axis=0, keepdims=True)
                acc[g, rows] = shrink * acc[g, rows] + _dot(
                    vt_ref[0, 0, rows], p.astype(dtype))
                top[r] = new
            return carry

        jax.lax.fori_loop(0, group, head, 0)

    _visited(qi, kb, block_q, block_k, attend)

    @pl.when(kb == _last_block(qi, block_q, block_k))
    def _():
        for g in range(group):
            for n in range(per_tile):
                r, rows = n * group + g, slice(n * dim, (n + 1) * dim)
                # every row sees at least itself
                o_ref[0, 0, g, rows] = (acc[g, rows] / total[r]).astype(o_ref.dtype)
                lse_ref[0, 0, r] = top[r] + jnp.log(total[r])


def _bwd_kernel(first_ref, q_ref, k_ref, kt_ref, v_ref, do_ref, lse_ref,
                delta_ref, segq_ref, segk_ref, dq_ref, dk_ref, dv_ref, dq_acc, *,
                scale: float, dim: int, per_tile: int):
    # dvtlint: traced
    qi, ki = pl.program_id(2), pl.program_id(3)
    kb = first_ref[pl.program_id(0), qi] + ki   # the step's block of keys
    group, block_q = q_ref.shape[2], q_ref.shape[3]
    block_k, width, dtype = k_ref.shape[2], k_ref.shape[3], q_ref.dtype

    @pl.when((qi == 0) & (ki == 0))
    def _():
        dk_ref[...] = jnp.zeros(dk_ref.shape, F32)
        dv_ref[...] = jnp.zeros(dv_ref.shape, F32)

    @pl.when(ki == 0)
    def _():
        dq_acc[...] = jnp.zeros(dq_acc.shape, F32)

    def visit(causal):
        bias = _bias(segq_ref, segk_ref, qi, kb, causal)
        keys = [_only_head(k_ref[0, 0], n, per_tile, dim) for n in range(per_tile)]
        values = [_only_head(v_ref[0, 0], n, per_tile, dim) for n in range(per_tile)]

        def head(g, carry):
            dk, dv = carry
            q, do = q_ref[0, 0, g], do_ref[0, 0, g]
            for n in range(per_tile):
                r, rows = n * group + g, slice(n * dim, (n + 1) * dim)
                s = _dot(keys[n], q, NT) * scale + bias
                p = jnp.exp(s - lse_ref[0, 0, r])
                dv = dv + _only_head(_dot(p.astype(dtype), do), n, per_tile, dim)
                dp = _dot(values[n], do, NT)
                # d(scores) less the scale, which the three sums take
                ds = (p * (dp - delta_ref[0, 0, r])).astype(dtype)
                dk = dk + _only_head(_dot(ds, q), n, per_tile, dim)
                dq_acc[g, rows] += _dot(kt_ref[0, 0, rows], ds)
            return dk, dv

        zero = jnp.zeros((block_k, width), F32)
        dk, dv = jax.lax.fori_loop(0, group, head, (zero, zero))
        here = pl.ds(pl.multiple_of(kb * block_k, block_k), block_k)
        dk_ref[0, 0, here] += dk * scale
        dv_ref[0, 0, here] += dv

    _visited(qi, kb, block_q, block_k, visit)

    @pl.when(kb == _last_block(qi, block_q, block_k))
    def _():
        dq_ref[0, 0] = (dq_acc[...] * scale).astype(dq_ref.dtype)


def _shapes(q, k, block: int):
    bsz, length, heads, dim = q.shape
    kv_heads = k.shape[2]
    if heads % kv_heads:
        raise ValueError(f"{heads} query heads over {kv_heads} key heads")
    per_tile = _heads_per_tile(kv_heads, dim)
    return (bsz, length, kv_heads // per_tile, per_tile, heads // kv_heads,
            dim, *_blocks(length, block))


def _gather_queries(x, tiles: int, per_tile: int, group: int):
    """(B, L, Hq, D) -> (B, tiles, group, L, per_tile * D): the query heads
    of a tile's key heads, one of each side by side along the lanes."""
    bsz, length, _, dim = x.shape
    x = x.reshape(bsz, length, tiles, per_tile, group, dim)
    return jnp.transpose(x, (0, 2, 4, 1, 3, 5)).reshape(
        bsz, tiles, group, length, per_tile * dim)


def _scatter_queries(xt, dim: int):
    """(B, tiles, group, per_tile * D, L), a tile transposed -> (B, L, Hq, D)."""
    bsz, tiles, group, width, length = xt.shape
    xt = xt.reshape(bsz, tiles, group, width // dim, dim, length)
    return jnp.transpose(xt, (0, 5, 1, 3, 2, 4)).reshape(
        bsz, length, tiles * group * (width // dim), dim)


def _gather_keys(x, tiles: int):
    """(B, L, Hkv, D) -> (B, tiles, L, per_tile * D)."""
    bsz, length, heads, dim = x.shape
    return jnp.swapaxes(x.reshape(bsz, length, tiles, heads // tiles * dim), 1, 2)


def _rows_of_heads(x, tiles: int):
    """(B, L, Hq) float32 -> (B, tiles, Hq / tiles, 1, L): a lane-dense row
    a head, in the heads' own order."""
    bsz, length, heads = x.shape
    return jnp.swapaxes(x, 1, 2).reshape(bsz, tiles, heads // tiles, 1, length)


def _specs(group, per_tile, width, length, block_q, block_k):
    """Block specs over the grid (row, tile of key heads, block of queries,
    step over its blocks of keys), every index map with the prefetched table
    of first blocks after the grid's indices: step ``ki`` takes block
    ``first[b, qi] + ki``, and a step past the last block visited takes the
    last one again, so that nothing is read for it."""
    def key(b, qi, ki, first):
        return jnp.minimum(first[b, qi] + ki, _last_block(qi, block_q, block_k))

    heads = group * per_tile
    return dict(
        q=pl.BlockSpec((1, 1, group, block_q, width),
                       lambda b, j, qi, ki, first: (b, j, 0, qi, 0)),
        qt=pl.BlockSpec((1, 1, group, width, block_q),
                        lambda b, j, qi, ki, first: (b, j, 0, 0, qi)),
        k=pl.BlockSpec(
            (1, 1, block_k, width),
            lambda b, j, qi, ki, first: (b, j, key(b, qi, ki, first), 0)),
        kt=pl.BlockSpec(
            (1, 1, width, block_k),
            lambda b, j, qi, ki, first: (b, j, 0, key(b, qi, ki, first))),
        row=pl.BlockSpec((1, 1, heads, 1, block_q),
                         lambda b, j, qi, ki, first: (b, j, 0, 0, qi)),
        segq=pl.BlockSpec((1, 1, block_q),
                          lambda b, j, qi, ki, first: (b, 0, qi)),
        segk=pl.BlockSpec(
            (1, block_k, 1),
            lambda b, j, qi, ki, first: (b, key(b, qi, ki, first), 0)),
        whole=pl.BlockSpec((1, 1, length, width),
                           lambda b, j, qi, ki, first: (b, j, 0, 0)))


def _compiler_params(carried: int, held: int):
    """Rows and tiles of key heads are independent; blocks of keys carry
    scratch, and in the backward kernel blocks of queries carry ``dk`` and
    ``dv`` (``carried`` grid dimensions from the last)."""
    semantics = ("parallel",) * (4 - carried) + ("arbitrary",) * carried
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=vmem_limit(held))


# jitted, so that a model's layers share one trace and one lowering of each
# kernel's body
@functools.partial(jax.jit, static_argnames=("scale", "block", "interpret"))
def _forward(q, k, v, segment_ids, scale: float, block: int, interpret: bool):
    """``out`` (B, L, Hq, D) in ``v``'s dtype and the scores' log-sum-exp
    (B, Hq, L) float32."""
    bsz, length, tiles, per_tile, group, dim, block_q, block_k = _shapes(q, k, block)
    width, heads = per_tile * dim, per_tile * group
    seg = segment_ids.astype(jnp.int32)
    s = _specs(group, per_tile, width, length, block_q, block_k)
    held = vmem_bytes(length, group, per_tile, dim, block_q, block_k,
                      q.dtype.itemsize)
    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, dim=dim, per_tile=per_tile),
        out_shape=(jax.ShapeDtypeStruct((bsz, tiles, group, width, length), v.dtype),
                   jax.ShapeDtypeStruct((bsz, tiles, heads, 1, length), F32)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(bsz, tiles, length // block_q, length // block_k),
            in_specs=[s["q"], s["k"], s["kt"], s["segq"], s["segk"]],
            out_specs=(s["qt"], s["row"]),
            scratch_shapes=[pltpu.VMEM((heads, 1, block_q), F32),
                            pltpu.VMEM((heads, 1, block_q), F32),
                            pltpu.VMEM((group, width, block_q), F32)]),
        name="causal_gqa_fwd", interpret=interpret,
        compiler_params=_compiler_params(1, held["causal_gqa_fwd"]),
    )(_first_blocks(seg, block_q, block_k),
      _gather_queries(q, tiles, per_tile, group), _gather_keys(k, tiles),
      jnp.swapaxes(_gather_keys(v, tiles), 2, 3), seg[:, None, :], seg[:, :, None])
    return _scatter_queries(out, dim), lse.reshape(bsz, tiles * heads, length)


@functools.partial(jax.jit, static_argnames=("scale", "block", "interpret"))
def _backward(q, k, v, segment_ids, out, lse, do, scale: float, block: int,
              interpret: bool):
    bsz, length, tiles, per_tile, group, dim, block_q, block_k = _shapes(q, k, block)
    width, heads = per_tile * dim, per_tile * group
    seg = segment_ids.astype(jnp.int32)
    delta = jnp.sum(do.astype(F32) * out.astype(F32), axis=-1)
    keys = _gather_keys(k, tiles)
    s = _specs(group, per_tile, width, length, block_q, block_k)
    whole = jax.ShapeDtypeStruct((bsz, tiles, length, width), F32)
    held = vmem_bytes(length, group, per_tile, dim, block_q, block_k,
                      q.dtype.itemsize)
    dq, dk, dv = pl.pallas_call(
        functools.partial(_bwd_kernel, scale=scale, dim=dim, per_tile=per_tile),
        out_shape=(jax.ShapeDtypeStruct((bsz, tiles, group, width, length), q.dtype),
                   whole, whole),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(bsz, tiles, length // block_q, length // block_k),
            in_specs=[s["q"], s["k"], s["kt"], s["k"], s["q"], s["row"],
                      s["row"], s["segq"], s["segk"]],
            out_specs=(s["qt"], s["whole"], s["whole"]),
            scratch_shapes=[pltpu.VMEM((group, width, block_q), F32)]),
        name="causal_gqa_bwd", interpret=interpret,
        compiler_params=_compiler_params(2, held["causal_gqa_bwd"]),
    )(_first_blocks(seg, block_q, block_k),
      _gather_queries(q, tiles, per_tile, group), keys, jnp.swapaxes(keys, 2, 3),
      _gather_keys(v, tiles), _gather_queries(do.astype(q.dtype), tiles, per_tile, group),
      lse.reshape(bsz, tiles, heads, 1, length), _rows_of_heads(delta, tiles),
      seg[:, None, :], seg[:, :, None])

    def keys_back(x, like):
        return jnp.swapaxes(x, 1, 2).reshape(like.shape).astype(like.dtype)

    return _scatter_queries(dq, dim), keys_back(dk, k), keys_back(dv, v), None


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def causal_attention(q, k, v, segment_ids, scale: float, block: int = 512):
    """``q`` (B, L, Hq, D); ``k``, ``v`` (B, L, Hkv, D) with Hq a multiple of
    Hkv; ``segment_ids`` (B, L); ``block`` an upper bound on the rows of
    queries and of keys a grid step takes, and a divisor of L.  Returns
    (B, L, Hq, D) in ``v``'s dtype."""
    return _forward(q, k, v, segment_ids, scale, block, _interpret())[0]


def _causal_attention_fwd(q, k, v, segment_ids, scale: float, block: int):
    out, lse = _forward(q, k, v, segment_ids, scale, block, _interpret())
    out, lse = checkpoint_name(out, OUT), checkpoint_name(lse, LSE)
    return out, (q, k, v, segment_ids, out, lse)


def _causal_attention_bwd(scale: float, block: int, kept, do):
    return _backward(*kept, do, scale, block, _interpret())


causal_attention.defvjp(_causal_attention_fwd, _causal_attention_bwd)
