"""``ops/attention.py``'s two kernels, interpreted, against a plain masked
softmax: heads of 16, rows of 64 in blocks of 16 or one block of 64, a
batch of two rows.  Row 0 of ``DOCUMENTS`` holds a document that starts on a
block's first row (16), one inside a block (20..22) and one that spans three
blocks (23..57); row 1 is one document.  ``packings`` adds a row in which
every token is its own document: the key blocks of earlier documents, which
the kernels skip, are all but the diagonal's there."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deep_vision_tpu.ops import attention
from deep_vision_tpu.ops.attention import causal_attention

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LENGTH, DIM = 64, 16
HEADS = [(4, 2), (4, 4), (8, 1)]
STARTS = [16, 20, 23, 58]
SCALE = 0.3   # no power of two


def documents():
    first = np.zeros((2, LENGTH), np.int32)
    first[0, STARTS] = 1
    return jnp.asarray(np.cumsum(first, axis=1, dtype=np.int32))


def inputs(heads, kv_heads, dtype=jnp.float32, seed=2):
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    return tuple(jax.random.normal(key, (2, LENGTH, n, DIM)).astype(dtype)
                 for key, n in zip(keys, (heads, kv_heads, kv_heads)))


def packings():
    """``documents()`` and a third row of ``LENGTH`` documents."""
    return jnp.concatenate(
        [documents(), jnp.arange(LENGTH, dtype=jnp.int32)[None]], axis=0)


def no_skipping(seg, block_q, block_k):
    """A table that starts every block of queries at the row's first block
    of keys: every block at or below the diagonal is visited."""
    return jnp.zeros((seg.shape[0], seg.shape[1] // block_q), jnp.int32)


def needed_blocks(seg, block_q, block_k):
    """(B, blocks of queries, blocks of keys) bool by brute force: some key
    of the block is visible to some query of the block."""
    seg = np.asarray(seg)
    at = np.arange(seg.shape[1])
    visible = (at[:, None] >= at[None, :]) & (seg[:, :, None] == seg[:, None, :])
    bsz, length = seg.shape
    return visible.reshape(bsz, length // block_q, block_q,
                           length // block_k, block_k).any(axis=(2, 4))


def plain(q, k, v, seg, scale):
    """Every score of the row at once, float32 at full precision."""
    q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
    group = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision="highest") * scale
    at = jnp.arange(q.shape[1])
    visible = (at[:, None] >= at[None, :]) & (seg[:, :, None] == seg[:, None, :])
    p = jax.nn.softmax(jnp.where(visible[:, None], s, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v, precision="highest")


def gradients(fn, q, k, v):
    return jax.grad(lambda *a: jnp.sum(jnp.sin(fn(*a).astype(jnp.float32))),
                    (0, 1, 2))(q, k, v)


@pytest.mark.parametrize("block", [16, 64], ids=["four_blocks", "one_block"])
@pytest.mark.parametrize("heads, kv_heads", HEADS)
def test_kernels_match_plain_masked_softmax(heads, kv_heads, block):
    """Float32, at ``tests/test_granite_hybrid.py``'s limits for the loop
    these kernels replaced."""
    seg = documents()
    q, k, v = inputs(heads, kv_heads)
    got = causal_attention(q, k, v, seg, SCALE, block)
    np.testing.assert_allclose(got, plain(q, k, v, seg, SCALE),
                               rtol=2e-5, atol=2e-5)
    got = gradients(lambda *a: causal_attention(*a, seg, SCALE, block), q, k, v)
    want = gradients(lambda *a: plain(*a, seg, SCALE), q, k, v)
    for g, w in zip(got, want):
        assert float(jnp.abs(w).max()) > 0.1
        np.testing.assert_allclose(g, w, rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("heads, kv_heads", HEADS)
def test_bfloat16_operands_are_the_float32_result_within_their_rounding(
        heads, kv_heads):
    """The same bfloat16 values through the kernels (bfloat16 operands,
    float32 scores, probabilities and ``ds`` rounded to bfloat16 before
    their products, results rounded to bfloat16) and through the plain form
    in float32: the output within 2e-2, a gradient within 3% of its largest
    entry (both are of order one here; bfloat16 keeps eight bits)."""
    seg = documents()
    q, k, v = inputs(heads, kv_heads, jnp.bfloat16)
    got = causal_attention(q, k, v, seg, SCALE, 16)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(got.astype(jnp.float32),
                               plain(q, k, v, seg, SCALE), atol=2e-2)
    got = gradients(lambda *a: causal_attention(*a, seg, SCALE, 16), q, k, v)
    want = gradients(lambda *a: plain(*a, seg, SCALE), q, k, v)
    for g, w in zip(got, want):
        assert g.dtype == jnp.bfloat16
        error = jnp.abs(g.astype(jnp.float32) - w.astype(jnp.float32)).max()
        assert float(error) <= 0.03 * float(jnp.abs(w).max())


@pytest.mark.parametrize("heads, kv_heads", HEADS)
def test_a_later_document_leaves_an_earlier_one_bit_identical(heads, kv_heads):
    seg = documents()
    q, k, v = inputs(heads, kv_heads)
    before = causal_attention(q, k, v, seg, SCALE, 16)
    later = (seg == 3)[:, :, None, None]   # rows 23..57 of row 0
    after = causal_attention(q, jnp.where(later, k + 1.5, k),
                             jnp.where(later, 2.0 * v, v), seg, SCALE, 16)
    earlier = np.asarray(seg < 3) & (np.arange(LENGTH) < STARTS[2])
    moved = np.abs(np.asarray(after - before)).max(axis=(2, 3))
    assert moved[np.asarray(later[:, :, 0, 0])].min() > 1e-4
    assert np.array_equal(np.asarray(after)[earlier], np.asarray(before)[earlier])


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_heads_of_256_with_group_one_match_the_plain_form(dtype):
    """The latent-attention cell's layout: every query head its own key
    head, a head two 128-lane tiles wide (one head a grid step, no lanes
    zeroed), scale 1/16; forward and backward, at the float32 test's and at
    the bfloat16 test's limits (the scores sum 256 products, so the values
    are drawn at a quarter of the other tests')."""
    seg = documents()
    keys = jax.random.split(jax.random.PRNGKey(6), 3)
    q, k, v = (jax.random.normal(key, (2, LENGTH, 3, 256)).astype(dtype)
               for key in keys)
    assert attention._heads_per_tile(3, 256) == 1
    got = causal_attention(q, k, v, seg, 1 / 16, 16)
    want = plain(q, k, v, seg, 1 / 16)
    assert got.dtype == dtype and float(jnp.abs(want).max()) > 1.0
    got_g = gradients(lambda *a: causal_attention(*a, seg, 1 / 16, 16), q, k, v)
    want_g = gradients(lambda *a: plain(*a, seg, 1 / 16), q, k, v)
    if dtype == jnp.float32:
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
        for g, w in zip(got_g, want_g):
            assert float(jnp.abs(w).max()) > 0.1
            np.testing.assert_allclose(g, w, rtol=2e-4, atol=2e-5)
    else:
        np.testing.assert_allclose(got.astype(jnp.float32), want, atol=3e-2)
        for g, w in zip(got_g, want_g):
            assert g.dtype == jnp.bfloat16
            error = jnp.abs(g.astype(jnp.float32) - w.astype(jnp.float32)).max()
            assert float(error) <= 0.03 * float(jnp.abs(w).max())


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("heads, kv_heads, dim",
                         [(*h, DIM) for h in HEADS] + [(3, 3, 256)])
def test_skipping_earlier_documents_changes_no_bit(monkeypatch, heads, kv_heads,
                                                   dim, dtype):
    """Both kernels with the table computed from the ids and with a table of
    zeros (every causal block visited): the output, the log-sum-exp and the
    three cotangents are the same bits, on rows whose documents end on a
    block's edge, inside a block, span several blocks, fill the row, and are
    one token each."""
    seg = packings()
    keys = jax.random.split(jax.random.PRNGKey(4), 4)
    q, k, v, do = (jax.random.normal(key, (3, LENGTH, n, dim)).astype(dtype)
                   for key, n in zip(keys, (heads, kv_heads, kv_heads, heads)))
    first = attention._first_blocks(seg, 16, 16)
    assert first[0].tolist() == [0, 1, 1, 1] and first[2].tolist() == [0, 1, 2, 3]
    assert not first[1].any()
    got = {}
    for name, table in [("computed", attention._first_blocks),
                        ("zeros", no_skipping)]:
        monkeypatch.setattr(attention, "_first_blocks", table)
        # traced afresh: the module's jitted halves keep their first trace
        static = ("scale", "block", "interpret")
        forward = jax.jit(attention._forward.__wrapped__, static_argnames=static)
        backward = jax.jit(attention._backward.__wrapped__, static_argnames=static)
        out, lse = forward(q, k, v, seg, SCALE, 16, True)
        got[name] = (out, lse) + backward(q, k, v, seg, out, lse, do, SCALE,
                                          16, True)[:3]
    for a, b in zip(got["computed"], got["zeros"]):
        assert a.dtype == b.dtype and float(jnp.abs(a.astype(jnp.float32)).max()) > 0.1
        assert np.array_equal(np.asarray(a.astype(jnp.float32)),
                              np.asarray(b.astype(jnp.float32)))


@pytest.mark.parametrize("ids", ["falling", "shuffled", "returning"])
@pytest.mark.parametrize("heads, kv_heads", HEADS[:2])
def test_ids_in_any_order_match_the_plain_masked_softmax(heads, kv_heads, ids):
    """The table of first blocks only ever drops blocks none of whose keys
    shares an id with a query of the block, so ids that fall, ids drawn a
    token at a time and a document that comes back after another are masked
    as the plain form masks them, values and gradients."""
    rng = np.random.default_rng(5)
    rising = np.asarray(documents())
    seg = {"falling": rising.max() - rising,
           "shuffled": rng.integers(0, 3, size=(2, LENGTH)),
           "returning": np.where((np.arange(LENGTH) // 8) % 3 == 1, 7, rising),
           }[ids].astype(np.int32)
    first = np.asarray(attention._first_blocks(seg, 16, 16))
    if ids == "returning":
        assert first[0].tolist() == [0, 0, 0, 0] and first[1].tolist() == [0, 0, 0, 0]
    seg = jnp.asarray(seg)
    q, k, v = inputs(heads, kv_heads)
    got = causal_attention(q, k, v, seg, SCALE, 16)
    np.testing.assert_allclose(got, plain(q, k, v, seg, SCALE),
                               rtol=2e-5, atol=2e-5)
    got = gradients(lambda *a: causal_attention(*a, seg, SCALE, 16), q, k, v)
    want = gradients(lambda *a: plain(*a, seg, SCALE), q, k, v)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("block_q, block_k", [(16, 16), (32, 16), (16, 32), (8, 64)])
def test_first_block_against_every_visible_pair(block_q, block_k):
    """On random packings (ids that count up) the blocks from the table's to
    the diagonal's are exactly those that hold a visible pair; on ids in any
    order no block that holds one lies before the table's.  As traced values
    and as NumPy arrays the table is the same."""
    rng = np.random.default_rng(7)
    starts = rng.random((6, 256)) < rng.choice([0.01, 0.03, 0.2], size=(6, 1))
    rising = np.cumsum(starts, axis=1).astype(np.int32)
    any_order = rng.integers(0, 4, size=(6, 256)).astype(np.int32)
    at = np.arange(256 // block_k)
    last = attention._last_block(np.arange(256 // block_q), block_q, block_k)
    for seg, exact in [(rising, True), (any_order, False)]:
        first = attention._first_blocks(seg, block_q, block_k)
        assert isinstance(first, np.ndarray) and first.dtype == np.int32
        assert np.array_equal(
            first, jax.jit(attention._first_blocks, static_argnums=(1, 2))(
                jnp.asarray(seg), block_q, block_k))
        assert (first <= last).all()
        visited = (at >= first[:, :, None]) & (at <= last[:, None])
        needed = needed_blocks(seg, block_q, block_k)
        assert not (needed & ~visited).any()
        if exact:
            assert np.array_equal(needed, visited)
            # the packings leave work to skip and work to do
            causal = len(seg) * (at <= last[:, None]).sum()
            assert 0.2 < needed.sum() / causal < 0.8


@pytest.mark.parametrize("block", [16, 32, 64])
def test_host_count_is_what_the_kernels_admit(block):
    """``visited_blocks`` against the grid as the kernels walk it: the op's
    own table, prefetched, and the op's own ``_visited`` over the op's grid,
    each admitted step counted once."""
    seg = packings()
    block_q, block_k = attention._blocks(LENGTH, block)
    grid = (seg.shape[0], LENGTH // block_q, LENGTH // block_k)

    def kernel(first_ref, o_ref):
        qi = pl.program_id(1)
        kb = first_ref[pl.program_id(0), qi] + pl.program_id(2)
        o_ref[...] = jnp.zeros(o_ref.shape, jnp.int32)

        def count(causal):
            o_ref[...] += 1

        attention._visited(qi, kb, block_q, block_k, count)

    admitted = pl.pallas_call(
        kernel, out_shape=jax.ShapeDtypeStruct(grid, jnp.int32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=grid, in_specs=[],
            out_specs=pl.BlockSpec((1, 1, 1), lambda b, qi, ki, first: (b, qi, ki))),
        interpret=True)(attention._first_blocks(seg, block_q, block_k))
    assert int(admitted.max()) == 1
    visited, causal = attention.visited_blocks(seg, block)
    assert visited == int(admitted.sum())
    per_row = sum(int(attention._last_block(qi, block_q, block_k)) + 1
                  for qi in range(grid[1]))
    assert causal == 3 * per_row
    assert visited == int(needed_blocks(seg, block_q, block_k).sum())
    assert attention.visited_blocks(seg[1:2], block) == (per_row, per_row)
    assert attention.visited_blocks(seg[2:], block) == (grid[1], per_row)


def test_block_counts_at_the_cells_rows():
    """Rows of 8,192 at the op's default block: 136 pairs of 512 x 512 at or
    below the diagonal, all of them for one document, the diagonal's 16 where
    every token is its own."""
    assert attention.visited_blocks(np.zeros((1, 8192), np.int32)) == (136, 136)
    assert attention.visited_blocks(np.arange(8192, dtype=np.int32)[None]) == (16, 136)
    assert attention.visited_blocks(np.zeros((2, 4096), np.int32)) == (72, 72)


def test_heads_that_do_not_divide_and_blocks_that_do_not_are_refused():
    q, k, v = inputs(4, 2)
    with pytest.raises(ValueError, match="query heads"):
        causal_attention(q, k[:, :, :1].repeat(3, axis=2), v, documents(), SCALE, 16)
    with pytest.raises(ValueError, match="not a multiple"):
        causal_attention(q, k, v, documents(), SCALE, 48)


def test_no_block_by_block_face_outside_the_kernels(jaxpr_equations):
    """Forward and backward, a block's scores and probabilities exist inside
    the two ``pallas_call``s alone, and ``k`` and ``v`` are never repeated to
    the query heads' count: no value around the kernels has a
    ``(block, block)`` face or more elements than ``q``.  Heads of 32 and
    blocks of 16, so that no other pair of dimensions reads as one."""
    block, seg = 16, documents()
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    q, k, v = (jax.random.normal(key, (2, LENGTH, n, 32))
               for key, n in zip(keys, (4, 2, 2)))

    def loss(q, k, v):
        return jnp.sum(causal_attention(q, k, v, seg, SCALE, block))

    jaxpr = jax.make_jaxpr(jax.grad(loss, (0, 1, 2)))(q, k, v)
    outside = list(jaxpr_equations(jaxpr.jaxpr, closed=("pallas_call",)))
    kernels = [e.params["name"] for e in outside
               if e.primitive.name == "pallas_call"]
    assert kernels == ["causal_gqa_fwd", "causal_gqa_bwd"]
    shapes = [v.aval.shape for e in outside for v in e.invars + e.outvars
              if hasattr(v.aval, "shape")]
    assert shapes and not any(s[-2:] == (block, block) for s in shapes)
    assert max(int(np.prod(s)) for s in shapes) == q.size


@pytest.mark.parametrize("cell, model_block", [
    ("LFM2-24B-A2B", 1024), ("granite-4.0-h-micro", 512)])
def test_blocks_and_vmem_at_the_cells_shapes(cell, model_block):
    """At the rows and heads of the two language cells the kernels' blocks
    divide the row, fill whole 128-lane tiles, and what the kernels hold in
    VMEM by the module's own reckoning is under the limit they ask for, which
    is a quarter of a v5e core's 128 MiB at most."""
    with open(os.path.join(ROOT, "benchmark", "configs", cell + ".json")) as f:
        cfg = json.load(f)
    length = cfg["sequence_length"]
    heads, kv_heads = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dim = cfg.get("head_dim") or cfg["hidden_size"] // heads
    assert (length, heads, kv_heads, dim) == (
        {"LFM2-24B-A2B": 8192, "granite-4.0-h-micro": 4096}[cell], 32, 8, 64)
    per_tile = attention._heads_per_tile(kv_heads, dim)
    block_q, block_k = attention._blocks(length, model_block)
    assert per_tile * dim == attention.LANE
    assert length % block_q == 0 and length % block_k == 0
    assert block_q % attention.LANE == 0 and block_k % 16 == 0
    assert max(block_q, block_k) <= model_block
    held = attention.vmem_bytes(length, heads // kv_heads, per_tile, dim,
                                block_q, block_k, itemsize=2)
    assert set(held) == {"causal_gqa_fwd", "causal_gqa_bwd"}
    for kernel, n in held.items():
        asked = attention.vmem_limit(n)
        assert 0 < n <= asked <= 32 * attention.MIB, (kernel, n, asked)
    # the forward kernel lives within Mosaic's default
    assert attention.vmem_limit(held["causal_gqa_fwd"]) == 16 * attention.MIB


def test_blocks_and_vmem_at_the_latent_attention_cells_shapes():
    """Rows of 8,192, 20 heads of 192 + 64 each with its own key head, the
    model's block: one head a grid step (two whole tiles wide), blocks of
    512 x 512, and the backward kernel, whose ``dk`` and ``dv`` for the whole
    row are 8 MiB each and held twice, asks for 44 MiB of a v5e core's 128."""
    from deep_vision_tpu.models.glm4_moe_lite import LatentAttention

    with open(os.path.join(ROOT, "benchmark", "configs", "GLM-4.7-Flash.json")) as f:
        cfg = json.load(f)
    length, heads = cfg["sequence_length"], cfg["num_attention_heads"]
    dim = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    assert (length, heads, cfg["num_key_value_heads"], dim, cfg["v_head_dim"]) == (
        8192, 20, 20, 256, 256)
    per_tile = attention._heads_per_tile(heads, dim)
    block_q, block_k = attention._blocks(length, LatentAttention.attention_block)
    assert per_tile == 1 and dim % attention.LANE == 0
    assert (block_q, block_k) == (512, 512)
    held = attention.vmem_bytes(length, 1, per_tile, dim, block_q, block_k,
                                itemsize=2)
    assert 2 * 2 * 4 * length * dim <= held["causal_gqa_bwd"]
    assert attention.vmem_limit(held["causal_gqa_fwd"]) == 16 * attention.MIB
    assert attention.vmem_limit(held["causal_gqa_bwd"]) == 44 * attention.MIB
