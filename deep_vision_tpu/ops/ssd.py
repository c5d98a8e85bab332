"""Chunked state-space scan (the Mamba-2 "SSD" form, Dao & Gu
arXiv:2405.21060 §6) over packed documents.

Per head, with a state ``H`` of ``(head_dim, state)`` that is zero before
each document's first token::

    H_t = exp(dt_t * a) * H_{t-1} + dt_t * x_t (outer) B_t
    y_t = H_t C_t

The recurrence is computed in chunks: inside a chunk the lower-triangular
``(C B^T) * decay`` product, between chunks the carried state.  ``dt``, the
cumulative sums, the decays and the state are float32; the large products
take their operands in ``x``'s dtype and accumulate in float32 (float32
operands are multiplied in full float32).

Documents are contiguous and ``segment_ids`` never decrease along a row
(``data/text.py::pack_documents`` yields them so), hence token ``s`` reaches
token ``t >= s`` exactly where their ids are equal: that one comparison is
the reset, inside a chunk and across chunks alike, wherever the boundary
falls on the chunk grid.

Two Pallas kernels under a ``jax.custom_vjp``, compiled by Mosaic on a TPU
and interpreted elsewhere.  ``ssd_chunk_fwd`` walks a row's chunks in order
and carries the state in VMEM; ``ssd_chunk_bwd`` walks them in reverse and
carries the state's cotangent.  A chunk's ``(chunk, chunk)`` decay, scores
and their product are built in VMEM in both and never reach HBM, which sees
the arguments, ``y``, each chunk's incoming state (float32, kept for the
backward pass) and the cotangents.  Around the kernels plain XLA does what
is small: ``dt``'s cumulative sums a chunk, the document marks, the layouts
the kernels read them in, and ``dt``'s and ``a``'s cotangents from the
kernel's.

Layout inside the kernels: heads lie along the lanes as in ``x`` viewed
``(L, H * P)``; a grid step takes a block of heads, and inside it works a
128-lane tile at a time (two heads of 64).  A head's ``(chunk, chunk)``
matrix multiplies the tile with the other heads' lanes zeroed, which costs
the matrix unit what a 64-wide product would.  The state is held transposed,
``(N, H * P)``, so that every product is a plain or an ``A B^T`` one.  A
value a head and position (``dt``, the sums) is read as a ``(chunk, 1)``
column and spread over its head's lanes with a select.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANE = 128
SUBLANE = 8
#: 128-lane tiles a grid step works through
TILES_PER_BLOCK = 8
F32 = jnp.float32
NN, NT, TN = ((1,), (0,)), ((1,), (1,)), ((0,), (0,))


def _dot(lhs, rhs, contract=NN):
    """Operands in the dtype they come in, float32 out; Mosaic's default
    for float32 operands is one bfloat16 pass, so those ask for all six."""
    precision = jax.lax.Precision.HIGHEST if lhs.dtype == F32 else None
    return jax.lax.dot_general(lhs, rhs, (contract, ((), ())),
                               precision=precision, preferred_element_type=F32)


def _blocking(heads: int, dim: int) -> tuple[int, int]:
    """Heads a 128-lane tile and tiles a grid step."""
    per_tile = max(1, LANE // dim)
    if heads % per_tile:
        raise ValueError(f"{heads} heads of {dim} do not fill whole "
                         f"{LANE}-lane tiles")
    tiles = heads // per_tile
    for n in range(min(TILES_PER_BLOCK, tiles), 0, -1):
        # a block of heads is the sublane side of the sums' row layout
        if tiles % n == 0 and (n * per_tile) % SUBLANE == 0:
            return per_tile, n
    return per_tile, tiles


def _lanes_of(lane, k: int, dim: int):
    """Where a tile's lanes are its head ``k``'s."""
    return (lane >= k * dim) & (lane < (k + 1) * dim)


def _spread(cols, first: int, per_tile: int, dim: int, lane):
    """``cols`` (R, heads of the block): column ``first + k`` on the lanes
    of the tile's head ``k``: (R, tile)."""
    out = cols[:, first:first + 1]
    for k in range(1, per_tile):
        out = jnp.where(lane >= k * dim, cols[:, first + k:first + k + 1], out)
    return out


def _gather(tile, first: int, per_tile: int, dim: int, lane, head):
    """The reverse: ``tile`` (R, tile) summed over each head's lanes into
    column ``first + k`` of (R, heads of the block), zero elsewhere."""
    out = 0.0
    for k in range(per_tile):
        col = jnp.sum(jnp.where(_lanes_of(lane, k, dim), tile, 0.0), axis=1,
                      keepdims=True)
        out = out + jnp.where(head == first + k, col, 0.0)
    return out


def _reach_bias(marks_ref, seg_row_ref, transposed: bool):
    """0 where the row's token is reached by the column's (same document,
    not later), -inf elsewhere; ``transposed`` swaps the two roles."""
    q = marks_ref.shape[1]
    rows = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    same = marks_ref[0, :, 0:1] == seg_row_ref[0, 0]
    order = cols >= rows if transposed else rows >= cols
    return jnp.where(same & order, 0.0, -jnp.inf)


def _chunk_factors(cumc, marks_ref):
    """Of a chunk, (Q, heads) and (1, heads): what the carried state
    contributes at each position, what each position leaves at the chunk's
    end, and what of the carried state is left there."""
    q = cumc.shape[0]
    last = cumc[q - 1:q]
    from_start = jnp.exp(cumc) * marks_ref[0, :, 1:2].astype(F32)
    to_end = jnp.exp(last - cumc) * marks_ref[0, :, 2:3].astype(F32)
    carried = jnp.exp(last) * marks_ref[0, q - 1:q, 1:2].astype(F32)
    return from_start, to_end, carried


def _fwd_kernel(x_ref, dt_ref, cumc_ref, cumr_ref, bt_ref, c_ref, marks_ref,
                seg_row_ref, y_ref, states_ref, state, scores, bias, *,
                dim: int, per_tile: int):
    # dvtlint: traced
    chunk_i, block_i = pl.program_id(1), pl.program_id(2)
    dtype, width = x_ref.dtype, per_tile * dim
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, width), 1)
    spread = functools.partial(_spread, per_tile=per_tile, dim=dim, lane=lane)

    @pl.when(block_i == 0)
    def _():
        bias[...] = _reach_bias(marks_ref, seg_row_ref, False)
        scores[...] = _dot(c_ref[0], bt_ref[0])

    @pl.when(chunk_i == 0)
    def _():
        state[block_i] = jnp.zeros(state.shape[1:], F32)

    dt, cumc, cumr = dt_ref[0, 0], cumc_ref[0, 0], cumr_ref[0]
    from_start, to_end, carried = _chunk_factors(cumc, marks_ref)
    for t in range(x_ref.shape[2] // width):
        lanes, first = slice(t * width, (t + 1) * width), t * per_tile
        xdt = x_ref[0, :, lanes].astype(F32) * spread(dt, first)
        before = state[block_i, :, lanes]
        states_ref[0, 0, :, lanes] = before
        y = spread(from_start, first) * _dot(c_ref[0], before.astype(dtype))
        for k in range(per_tile):
            h = first + k
            decay = jnp.exp(cumc[:, h:h + 1] - cumr[h:h + 1, :] + bias[...])
            y = y + _dot((scores[...] * decay).astype(dtype),
                         jnp.where(_lanes_of(lane, k, dim), xdt, 0.0).astype(dtype))
        y_ref[0, :, lanes] = y
        state[block_i, :, lanes] = spread(carried, first) * before + _dot(
            bt_ref[0], (xdt * spread(to_end, first)).astype(dtype))


def _bwd_kernel(x_ref, dt_ref, cumc_ref, cumr_ref, b_ref, bt_ref, c_ref, ct_ref,
                marks_ref, seg_row_ref, states_ref, y_ref, dy_ref,
                dx_ref, ddt_ref, dcum_ref, db_ref, dc_ref,
                dstate, scores_t, bias, bias_t, dscores, db_acc, dc_acc, *,
                dim: int, per_tile: int):
    # dvtlint: traced
    chunk_i, block_i = pl.program_id(1), pl.program_id(2)
    dtype, width, q = x_ref.dtype, per_tile * dim, x_ref.shape[1]
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, width), 1)
    head = jax.lax.broadcasted_iota(jnp.int32, (1, dt_ref.shape[3]), 1)
    spread = functools.partial(_spread, per_tile=per_tile, dim=dim, lane=lane)
    gather = functools.partial(_gather, per_tile=per_tile, dim=dim, lane=lane,
                               head=head)

    @pl.when(block_i == 0)
    def _():
        bias[...] = _reach_bias(marks_ref, seg_row_ref, False)
        bias_t[...] = _reach_bias(marks_ref, seg_row_ref, True)
        scores_t[...] = _dot(b_ref[0], ct_ref[0])
        dscores[...] = jnp.zeros(dscores.shape, F32)
        db_acc[...] = jnp.zeros(db_acc.shape, F32)
        dc_acc[...] = jnp.zeros(dc_acc.shape, F32)

    @pl.when(chunk_i == 0)
    def _():
        dstate[block_i] = jnp.zeros(dstate.shape[1:], F32)

    dt, cumc, cumr = dt_ref[0, 0], cumc_ref[0, 0], cumr_ref[0]
    from_start, to_end, carried = _chunk_factors(cumc, marks_ref)
    ddt, dcum = 0.0, 0.0
    for t in range(x_ref.shape[2] // width):
        lanes, first = slice(t * width, (t + 1) * width), t * per_tile
        x = x_ref[0, :, lanes].astype(F32)
        dt_e, to_end_e = spread(dt, first), spread(to_end, first)
        carried_e = spread(carried, first)
        xdt = x * dt_e
        operand = xdt.astype(dtype)
        dy = dy_ref[0, :, lanes]
        before = states_ref[0, 0, :, lanes]
        dafter = dstate[block_i, :, lanes]
        inside = 0.0
        for k in range(per_tile):
            h = first + k
            dy_h = jnp.where(_lanes_of(lane, k, dim), dy, 0.0).astype(dtype)
            decay = jnp.exp(cumc[:, h:h + 1] - cumr[h:h + 1, :] + bias[...])
            dscores[...] += decay * _dot(dy_h, operand, NT)
            decay_t = jnp.exp(cumr[h:h + 1, :] - cumc[:, h:h + 1] + bias_t[...])
            inside = inside + _dot((scores_t[...] * decay_t).astype(dtype), dy_h)
        # what the chunk's end takes of each position
        taken = to_end_e * _dot(b_ref[0], dafter.astype(dtype))
        dxdt = inside + taken
        dx_ref[0, :, lanes] = (dxdt * dt_e).astype(dx_ref.dtype)
        ddt = ddt + gather(dxdt * x, first)
        # the sums' cotangent: a decay's later end gains what its earlier
        # end loses, and the chunk's last sum also decays what the chunk
        # leaves and what it carries on.  The two ends of the decays inside
        # the chunk are sums of the same products (rows and columns of dM *
        # M) only with the operands as the matrix unit saw them, rounded
        at_end = jnp.sum(xdt * taken, axis=0, keepdims=True) + jnp.sum(
            carried_e * dafter * before, axis=0, keepdims=True)
        rows = jax.lax.broadcasted_iota(jnp.int32, (q, 1), 0)
        dcum = dcum + gather(
            dy.astype(dtype).astype(F32) * y_ref[0, :, lanes]
            - operand.astype(F32) * inside - xdt * taken, first) + (
                jnp.where(rows == q - 1, gather(at_end, first), 0.0))
        reads = (spread(from_start, first) * dy).astype(dtype)
        dc_acc[...] += _dot(reads, before.astype(dtype), NT)
        db_acc[...] += _dot((xdt * to_end_e).astype(dtype), dafter.astype(dtype), NT)
        dstate[block_i, :, lanes] = carried_e * dafter + _dot(ct_ref[0], reads)
    ddt_ref[0, 0] = ddt
    dcum_ref[0, 0] = dcum

    @pl.when(block_i == pl.num_programs(2) - 1)
    def _():
        ds = dscores[...].astype(dtype)
        dc_ref[0] = (dc_acc[...] + _dot(ds, b_ref[0])).astype(dc_ref.dtype)
        db_ref[0] = (db_acc[...] + _dot(ds, c_ref[0], TN)).astype(db_ref.dtype)


def _small_operands(dt, a, segment_ids, chunk: int, block: int):
    """What the kernels read beside ``x``, ``B`` and ``C``: ``dt`` and its
    cumulative sum a chunk as columns a block of heads (B, H / block, L,
    block), the sum as rows (B, H, L), the marks (B, L, 3) -- a position's
    document, whether it is the document the chunk before ended in, whether
    it is the one this chunk ends in -- and the documents as rows
    (B, chunks, 1, chunk)."""
    bsz, length, heads = dt.shape
    nc = length // chunk
    dt = dt.astype(F32)
    cum = jnp.cumsum((dt * a.astype(F32)).reshape(bsz, nc, chunk, heads),
                     axis=2).reshape(bsz, length, heads)

    def columns(v):
        return jnp.moveaxis(v.reshape(bsz, length, heads // block, block), 2, 1)

    seg = segment_ids.astype(jnp.int32).reshape(bsz, nc, chunk)
    last = seg[:, :, -1:]
    before = jnp.concatenate([last[:, :1], last[:, :-1]], axis=1)
    marks = jnp.stack([seg, seg == before, seg == last], axis=-1)
    return (columns(dt), columns(cum), jnp.moveaxis(cum, 2, 1),
            marks.reshape(bsz, length, 3), seg[:, :, None])


def _shapes(x, b, chunk: int):
    bsz, length, heads, dim = x.shape
    if length % chunk:
        raise ValueError(f"sequence length {length} is not a multiple of "
                         f"the scan's chunk {chunk}")
    per_tile, tiles = _blocking(heads, dim)
    return bsz, length, heads, dim, b.shape[-1], per_tile, per_tile * tiles


def _specs(dim, n, block, chunk, chunk_of):
    """Block specs by what they tile, over the grid (row, chunk, block of
    heads); ``chunk_of`` maps the grid's second index to the chunk."""
    def spec(shape, index):
        return pl.BlockSpec(shape, lambda r, i, g: index(r, chunk_of(i), g))

    return dict(
        wide=spec((1, chunk, block * dim), lambda r, i, g: (r, i, g)),
        column=spec((1, 1, chunk, block), lambda r, i, g: (r, g, i, 0)),
        row=spec((1, block, chunk), lambda r, i, g: (r, g, i)),
        state=spec((1, 1, n, block * dim), lambda r, i, g: (r, i, 0, g)),
        tokens=spec((1, chunk, n), lambda r, i, g: (r, i, 0)),
        tokens_t=spec((1, n, chunk), lambda r, i, g: (r, 0, i)),
        marks=spec((1, chunk, 3), lambda r, i, g: (r, i, 0)),
        seg_row=spec((1, 1, 1, chunk), lambda r, i, g: (r, i, 0, 0)))


def _interpret() -> bool:
    """Compiled by Mosaic on a TPU, interpreted elsewhere."""
    return jax.default_backend() != "tpu"


def _compiler_params():
    """Rows are independent, chunks and blocks of heads carry scratch.  The
    backward kernel's blocks, scratch and (chunk, chunk) temporaries take
    16-21 MiB at the published widths, over Mosaic's default of 16."""
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        vmem_limit_bytes=32 * 2 ** 20)


# jitted, so that a model's layers share one trace and one lowering of each
# kernel's unrolled body (seconds of host time a layer otherwise)
@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def _forward(x, dt, a, b, c, segment_ids, chunk: int, interpret: bool):
    """``y`` (B, L, H, P) and each chunk's incoming state, transposed:
    (B, chunks, N, H * P), both float32."""
    bsz, length, heads, dim, n, per_tile, block = _shapes(x, b, chunk)
    nc, groups = length // chunk, heads // block
    dtc, cumc, cumr, marks, seg_row = _small_operands(dt, a, segment_ids, chunk, block)
    s = _specs(dim, n, block, chunk, lambda i: i)
    y, states = pl.pallas_call(
        functools.partial(_fwd_kernel, dim=dim, per_tile=per_tile),
        out_shape=(jax.ShapeDtypeStruct((bsz, length, heads * dim), F32),
                   jax.ShapeDtypeStruct((bsz, nc, n, heads * dim), F32)),
        grid=(bsz, nc, groups),
        in_specs=[s["wide"], s["column"], s["column"], s["row"], s["tokens_t"],
                  s["tokens"], s["marks"], s["seg_row"]],
        out_specs=(s["wide"], s["state"]),
        scratch_shapes=[pltpu.VMEM((groups, n, block * dim), F32),
                        pltpu.VMEM((chunk, chunk), F32),
                        pltpu.VMEM((chunk, chunk), F32)],
        name="ssd_chunk_fwd", interpret=interpret,
        compiler_params=_compiler_params(),
    )(x.reshape(bsz, length, heads * dim), dtc, cumc, cumr,
      jnp.swapaxes(b, 1, 2), c, marks, seg_row)
    return y.reshape(x.shape), states


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def _backward(x, dt, a, b, c, segment_ids, y, states, dy, chunk: int,
              interpret: bool):
    bsz, length, heads, dim, n, per_tile, block = _shapes(x, b, chunk)
    nc, groups = length // chunk, heads // block
    dtc, cumc, cumr, marks, seg_row = _small_operands(dt, a, segment_ids, chunk, block)
    s = _specs(dim, n, block, chunk, lambda i: nc - 1 - i)
    wide = (bsz, length, heads * dim)
    square, tokens = pltpu.VMEM((chunk, chunk), F32), pltpu.VMEM((chunk, n), F32)
    dx, ddt, dcum, db, dc = pl.pallas_call(
        functools.partial(_bwd_kernel, dim=dim, per_tile=per_tile),
        out_shape=(jax.ShapeDtypeStruct(wide, x.dtype),
                   jax.ShapeDtypeStruct(dtc.shape, F32),
                   jax.ShapeDtypeStruct(dtc.shape, F32),
                   jax.ShapeDtypeStruct(b.shape, b.dtype),
                   jax.ShapeDtypeStruct(c.shape, c.dtype)),
        grid=(bsz, nc, groups),
        in_specs=[s["wide"], s["column"], s["column"], s["row"], s["tokens"],
                  s["tokens_t"], s["tokens"], s["tokens_t"], s["marks"],
                  s["seg_row"], s["state"], s["wide"], s["wide"]],
        out_specs=(s["wide"], s["column"], s["column"], s["tokens"], s["tokens"]),
        scratch_shapes=[pltpu.VMEM((groups, n, block * dim), F32),
                        square, square, square, square, tokens, tokens],
        name="ssd_chunk_bwd", interpret=interpret,
        compiler_params=_compiler_params(),
    )(x.reshape(wide), dtc, cumc, cumr, b, jnp.swapaxes(b, 1, 2), c,
      jnp.swapaxes(c, 1, 2), marks, seg_row, states, y.reshape(wide),
      dy.astype(F32).reshape(wide))

    def rows(v):
        return jnp.moveaxis(v, 1, 2).reshape(bsz, nc, chunk, heads)

    # a position's dt * a enters every later sum of its chunk
    dsteps = jnp.flip(jnp.cumsum(jnp.flip(rows(dcum), 2), axis=2), 2)
    dt32 = dt.astype(F32).reshape(dsteps.shape)
    ddt = rows(ddt) + dsteps * a.astype(F32)
    da = jnp.sum(dsteps * dt32, axis=(0, 1, 2))
    return (dx.reshape(x.shape), ddt.reshape(dt.shape).astype(dt.dtype),
            da.astype(a.dtype), db, dc, None)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def ssd_scan(x, dt, a, b, c, segment_ids, chunk: int):
    """``x`` (B, L, H, P); ``dt`` (B, L, H) float32, positive; ``a`` (H,)
    float32, negative; ``b``, ``c`` (B, L, N), one group shared by the heads;
    ``segment_ids`` (B, L) integers.  Returns ``y`` (B, L, H, P) float32."""
    return _forward(x, dt, a, b, c, segment_ids, chunk, _interpret())[0]


def _ssd_scan_fwd(x, dt, a, b, c, segment_ids, chunk: int):
    y, states = _forward(x, dt, a, b, c, segment_ids, chunk, _interpret())
    return y, (x, dt, a, b, c, segment_ids, y, states)


def _ssd_scan_bwd(chunk: int, kept, dy):
    return _backward(*kept, dy, chunk, _interpret())


ssd_scan.defvjp(_ssd_scan_fwd, _ssd_scan_bwd)
