"""The hybrid state-space decoder at a small size on the CPU: hidden 64,
4 heads of 16, state 16, chunk 8, rows of 64, three layers
mamba / attention / mamba, 128 rows of vocabulary.  The chunked scan and the
attention kernels against their plain forms, the model against the
benchmark's plain reference, and what ties a packed row's documents apart."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deep_vision_tpu.data.text import pack_documents, synthetic_corpus
from deep_vision_tpu.models import granite_hybrid
from deep_vision_tpu.models.granite_hybrid import (
    GraniteHybrid,
    GraniteHybridConfig,
)
from deep_vision_tpu.ops.attention import causal_attention
from deep_vision_tpu.ops.ssd import ssd_scan
from deep_vision_tpu.zoo.language import GRANITE_4_0_H_MICRO

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMALL = dict(GRANITE_4_0_H_MICRO, hidden_size=64, num_attention_heads=4,
             num_key_value_heads=2, mamba_n_heads=8, mamba_d_head=16,
             mamba_d_state=16, mamba_chunk_size=8, shared_intermediate_size=128,
             layer_types=["mamba", "attention", "mamba"], num_hidden_layers=3,
             vocab_size=128)
LENGTH = 64


def small_rows(seed=3, rows=2):
    """Packed rows whose document boundaries fall off the chunk grid."""
    docs = synthetic_corpus(LENGTH * (rows + 1), SMALL["vocab_size"], seed=seed,
                            median_length=11, sigma=0.6, max_length=LENGTH)
    batch = pack_documents(docs, LENGTH)
    starts = np.flatnonzero(np.diff(batch["segment_ids"][0])) + 1
    assert len(starts) >= 2 and any(s % SMALL["mamba_chunk_size"] for s in starts)
    return {k: v[:rows] for k, v in batch.items()}


def small_model(dtype=jnp.float32):
    return GraniteHybrid(GraniteHybridConfig.from_dict(SMALL),
                         attention_block=16, dtype=dtype)


def flat(tree):
    from flax import traverse_util

    return traverse_util.flatten_dict(dict(tree), sep="/")


@pytest.fixture(scope="module")
def reference():
    from benchmark.byname import load_module

    module = load_module(os.path.join(
        ROOT, "benchmark", "configs", "granite-4.0-h-micro.py"), "granite_ref")
    return module, module.Reference(SMALL)


def scan_documents(rows):
    """Two rows of ``LENGTH`` segment ids: boundaries off the chunk grid of
    8 (the packed rows), none at all, or all of them on it."""
    if rows == "off_the_grid":
        return jnp.asarray(small_rows()["segment_ids"])
    if rows == "one_document":
        return jnp.zeros((2, LENGTH), jnp.int32)
    starts = np.zeros((2, LENGTH), np.int32)
    starts[0, [16, 32, 40]] = 1
    starts[1, [8, 48]] = 1
    return jnp.asarray(np.cumsum(starts, axis=1, dtype=np.int32))


def scan_inputs(seed=0, heads=8, dim=16, n=16, rows="off_the_grid"):
    seg = scan_documents(rows)
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    shape = seg.shape
    x = jax.random.normal(keys[0], shape + (heads, dim))
    dt = jax.nn.softplus(jax.random.normal(keys[1], shape + (heads,)) - 1.0)
    a = -jnp.exp(jax.random.uniform(keys[2], (heads,), minval=0.0, maxval=2.0))
    b = jax.random.normal(keys[3], shape + (n,))
    c = jax.random.normal(keys[4], shape + (n,))
    return x, dt, a, b, c, seg


def in_bfloat16(x, dt, a, b, c):
    """The scan's arguments as the model hands them over in bfloat16, and
    the same values in float32."""
    low = (x.astype(jnp.bfloat16), dt, a, b.astype(jnp.bfloat16),
           c.astype(jnp.bfloat16))
    return low, tuple(v.astype(jnp.float32) for v in low)


ROWS = ["off_the_grid", "one_document", "on_the_grid"]


def sequential_scan(x, dt, a, b, c, seg):
    """The recurrence as written, one token at a time, in NumPy float64."""
    x, dt, a, b, c = (np.asarray(v, np.float64) for v in (x, dt, a, b, c))
    out = np.zeros_like(x)
    for r in range(x.shape[0]):
        state = np.zeros(x.shape[2:] + (b.shape[-1],))
        for t in range(x.shape[1]):
            if t == 0 or seg[r, t] != seg[r, t - 1]:
                state[:] = 0.0
            state = (np.exp(dt[r, t] * a)[:, None, None] * state
                     + (dt[r, t][:, None] * x[r, t])[:, :, None] * b[r, t])
            out[r, t] = state @ c[r, t]
    return out


@pytest.mark.parametrize("rows", ROWS)
def test_chunked_scan_matches_the_sequential_recurrence(rows):
    x, dt, a, b, c, seg = scan_inputs(rows=rows)
    got = ssd_scan(x, dt, a, b, c, seg, chunk=8)
    want = sequential_scan(x, dt, a, b, c, np.asarray(seg))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def relative(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.linalg.norm(got - want) / np.linalg.norm(want)


def test_chunked_scan_in_bfloat16_is_the_float32_scan_within_its_rounding():
    """Operands of the products rounded to 8 bits of mantissa, sums and
    decays and state in float32: the result stays float32 and lies within
    a few 2^-9 of the float32 scan of the same values, by norm."""
    *args, seg = scan_inputs()
    low, full = in_bfloat16(*args)
    got = ssd_scan(*low, seg, chunk=8)
    assert got.dtype == jnp.float32
    assert relative(got, ssd_scan(*full, seg, chunk=8)) < 4e-3


def scan_gradients(args, seg, weights):
    def loss(*args):
        return jnp.sum(weights * ssd_scan(*args, seg, chunk=8))

    return jax.grad(loss, argnums=(0, 1, 2, 3, 4))(*args)


@pytest.mark.parametrize("rows", ROWS)
def test_chunked_scan_gradients_match_the_sequential_recurrence(reference, rows):
    module, _ = reference
    x, dt, a, b, c, seg = scan_inputs(seed=1, rows=rows)
    first = jnp.concatenate([jnp.ones((seg.shape[0], 1), bool),
                             seg[:, 1:] != seg[:, :-1]], axis=1)
    weights = jax.random.normal(jax.random.PRNGKey(9), x.shape)

    def sequential(x, dt, a, b, c):
        y = jax.vmap(module._scan, in_axes=(0, 0, None, 0, 0, 0))(
            x, dt, a, b, c, first)
        return jnp.sum(weights * y)

    got = scan_gradients((x, dt, a, b, c), seg, weights)
    want = jax.grad(sequential, argnums=(0, 1, 2, 3, 4))(x, dt, a, b, c)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, rtol=2e-3, atol=2e-3)


def test_chunked_scan_gradients_in_bfloat16_are_the_float32_ones_within_rounding():
    """Each cotangent in its argument's dtype, and within bfloat16's
    rounding of the float32 kernel's, by norm."""
    *args, seg = scan_inputs(seed=1)
    low, full = in_bfloat16(*args)
    weights = jax.random.normal(jax.random.PRNGKey(9), args[0].shape)
    got = scan_gradients(low, seg, weights)
    want = scan_gradients(full, seg, weights)
    for g, w, arg in zip(got, want, low):
        assert g.dtype == arg.dtype and g.shape == arg.shape
        assert relative(g.astype(jnp.float32), w) < 1e-2


def test_no_chunk_by_chunk_face_outside_the_kernels(jaxpr_equations):
    """Forward and backward, the decays and scores of a chunk exist inside
    the two ``pallas_call``s alone: no equation around them reads or writes
    a value whose last two dimensions are ``(chunk, chunk)``.  Chunk 16 of
    4 chunks, 8 heads of 32, state 24, so that no other pair of dimensions
    reads as one."""
    chunk = 16
    x, dt, a, b, c, seg = scan_inputs(dim=32, n=24)

    def loss(x, dt, a, b, c):
        return jnp.sum(ssd_scan(x, dt, a, b, c, seg, chunk))

    jaxpr = jax.make_jaxpr(jax.grad(loss, (0, 1, 2, 3, 4)))(x, dt, a, b, c)
    outside = list(jaxpr_equations(jaxpr.jaxpr, closed=("pallas_call",)))
    kernels = [e.params["name"] for e in outside
               if e.primitive.name == "pallas_call"]
    assert kernels == ["ssd_chunk_fwd", "ssd_chunk_bwd"]
    faces = [v.aval.shape[-2:] for e in outside for v in e.invars + e.outvars
             if hasattr(v.aval, "shape")]
    assert faces and (chunk, chunk) not in faces


def test_blocked_attention_matches_plain_masked_softmax(reference):
    module, _ = reference
    seg = jnp.asarray(small_rows()["segment_ids"])
    keys = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(keys[0], seg.shape + (4, 16))
    k = jax.random.normal(keys[1], seg.shape + (2, 16))
    v = jax.random.normal(keys[2], seg.shape + (2, 16))

    def blocked(q, k, v):
        return causal_attention(q, k, v, seg, 0.25, block=16)

    def plain(q, k, v):
        return jax.vmap(lambda q, k, v, s: module._attention(q, k, v, s, 0.25))(
            q, k, v, seg)

    np.testing.assert_allclose(blocked(q, k, v), plain(q, k, v),
                               rtol=2e-5, atol=2e-5)
    got = jax.grad(lambda *a: jnp.sum(jnp.sin(blocked(*a))), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(jnp.sin(plain(*a))), (0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, rtol=2e-4, atol=2e-5)


def test_logits_match_the_reference_on_seeded_weights(reference):
    _, ref = reference
    batch = small_rows()
    model = small_model()
    tokens, seg = jnp.asarray(batch["tokens"]), jnp.asarray(batch["segment_ids"])
    params = model.init(jax.random.PRNGKey(5), tokens, seg)["params"]
    got = model.apply({"params": params}, tokens, seg)
    want = ref.logits(flat(params), tokens, seg)
    assert float(jnp.std(want)) > 0.01
    np.testing.assert_allclose(got, want, rtol=1e-3, atol=1e-4)


def test_a_document_sees_nothing_of_its_neighbours():
    """Changing one document's tokens leaves every other document's logits
    as they were: the conv's taps, the scan's reset and the attention mask
    all hold at once."""
    batch = small_rows()
    model = small_model()
    tokens, seg = jnp.asarray(batch["tokens"]), jnp.asarray(batch["segment_ids"])
    params = model.init(jax.random.PRNGKey(6), tokens, seg)["params"]
    before = model.apply({"params": params}, tokens, seg)
    changed = (seg == 1)
    other = jnp.where(changed, (tokens + 7) % SMALL["vocab_size"], tokens)
    after = model.apply({"params": params}, other, seg)
    moved = np.abs(np.asarray(after - before)).max(-1)
    assert moved[np.asarray(changed)].min() > 1e-4
    assert moved[~np.asarray(changed)].max() == 0.0


def remat_layer(kind):
    """One rematerialised layer, its float32 loss over ``(params, h)`` and
    those arguments."""
    cfg = GraniteHybridConfig.from_dict(SMALL)
    seg = jnp.asarray(small_rows()["segment_ids"])
    h = jax.random.normal(jax.random.PRNGKey(7), seg.shape + (cfg.hidden_size,))
    layer = granite_hybrid.RematLayer(cfg, kind, 16, jnp.float32)
    params = layer.init(jax.random.PRNGKey(8), h, seg)["params"]

    def loss(params, h):
        return jnp.sum(jnp.sin(layer.apply({"params": params}, h, seg)))

    return cfg, loss, params, h


@pytest.mark.parametrize("kind, dense", [("mamba", 4), ("attention", 6)])
def test_backward_pass_runs_no_dense_product_twice(kind, dense,
                                                   jaxpr_equations):
    """A product against a weight matrix is the one ``dot_general`` without
    batch dimensions outside the scan's and the attention's kernels (the
    kernels' two-dimensional ones are theirs): forward, dx and dW a
    ``Dense`` and no fourth.  With a bare ``nn.remat`` the counts were 15
    and 23."""
    _, loss, params, h = remat_layer(kind)
    jaxpr = jax.make_jaxpr(jax.grad(loss, (0, 1)))(params, h)
    products = [e for e in jaxpr_equations(jaxpr.jaxpr, closed=("pallas_call",))
                if e.primitive.name == "dot_general"
                and not e.params["dimension_numbers"][1][0]]
    assert len(products) == 3 * dense
    # the scan's result and states are not kept (PERF.md §6, PR 32: keeping
    # them cost more in layout copies than the forward kernel they save):
    # the forward kernel runs again under the recomputation.  The
    # attention's output and log-sum-exp are kept (PR 34), so its forward
    # kernel runs once
    kernels = [e.params["name"] for e in jaxpr_equations(jaxpr.jaxpr)
               if e.primitive.name == "pallas_call"]
    assert kernels == (["ssd_chunk_fwd", "ssd_chunk_fwd", "ssd_chunk_bwd"]
                       if kind == "mamba"
                       else ["causal_gqa_fwd", "causal_gqa_bwd"])


@pytest.mark.parametrize("kind", ["mamba", "attention"])
def test_layer_keeps_its_input_and_its_dense_outputs(kind, kept_between_passes):
    """Beside parameters, constants and the layer's input (and the cosine
    this test's own loss keeps), what is kept between the passes is what
    ``KEPT`` names: the output of each product against a weight matrix that
    the backward pass reads, the attention kernel's output and its float32
    log-sum-exp a row and head, and nothing with a ``(chunk, chunk)`` face."""
    cfg, loss, params, h = remat_layer(kind)
    kept = [shape for _, shape in kept_between_passes(loss, params, h)]
    rows = h.shape[:2]
    mlp = rows + (2 * cfg.shared_intermediate_size,)
    if kind == "mamba":
        want = [rows + (2 * cfg.d_inner + 2 * cfg.mamba_d_state + cfg.mamba_n_heads,),
                rows + (cfg.hidden_size,), mlp]
    else:
        kv = rows + (cfg.num_key_value_heads * cfg.head_dim,)
        heads = rows + (cfg.num_attention_heads, cfg.head_dim)
        want = [rows + (cfg.hidden_size,), kv, kv, heads,
                (rows[0], cfg.num_attention_heads, rows[1]),
                rows + (cfg.hidden_size,), mlp]
    assert kept == want
    chunk = cfg.mamba_chunk_size
    assert not any(shape[-2:] == (chunk, chunk) for shape in kept)


def test_gradients_equal_those_of_the_model_without_remat(monkeypatch):
    batch = small_rows()
    tokens, seg = jnp.asarray(batch["tokens"]), jnp.asarray(batch["segment_ids"])
    model = GraniteHybrid(GraniteHybridConfig.from_dict(dict(
        SMALL, layer_types=["mamba", "attention"], num_hidden_layers=2)),
        attention_block=16, dtype=jnp.float32)
    params = model.init(jax.random.PRNGKey(4), tokens, seg)["params"]

    def grads():
        return flat(jax.grad(lambda p: jnp.sum(jnp.sin(
            model.apply({"params": p}, tokens, seg))))(params))

    got = grads()
    monkeypatch.setattr(granite_hybrid, "RematLayer", granite_hybrid.GraniteLayer)
    want = grads()
    assert got.keys() == want.keys()
    for leaf, w in want.items():
        assert float(jnp.linalg.norm(w)) > 0
        assert float(jnp.linalg.norm(got[leaf] - w)) <= 1e-6 * float(
            jnp.linalg.norm(w)), leaf


def test_published_config_has_3_19_billion_parameters():
    model = GraniteHybrid(GraniteHybridConfig.from_dict(GRANITE_4_0_H_MICRO))
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), jnp.zeros((1, 256), jnp.int32),
                           jnp.zeros((1, 256), jnp.int32)))
    n = sum(int(np.prod(v.shape)) for v in jax.tree_util.tree_leaves(shapes))
    assert abs(n - 3.19e9) < 0.01 * 3.19e9
    assert len(GRANITE_4_0_H_MICRO["layer_types"]) == 40
    assert GRANITE_4_0_H_MICRO["layer_types"].count("attention") == 4


def test_benchmark_file_differs_from_the_published_config_only_where_it_says():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "granite-4.0-h-micro.json")) as f:
        cell = json.load(f)
    assert cell["reduced"] == ["num_hidden_layers", "vocab_size"]
    differs = [k for k, v in GRANITE_4_0_H_MICRO.items() if cell[k] != v]
    assert sorted(differs) == cell["reduced"]
    assert cell["published"] == {k: GRANITE_4_0_H_MICRO[k] for k in cell["reduced"]}
    assert cell["num_hidden_layers"] == 10 and cell["vocab_size"] * 8 == 100352


def test_zoo_holds_the_catalogs_config_key_for_key():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("the catalog of public architectures is not on this machine")
    with open(catalog) as f:
        rows = [json.loads(line) for line in f if line.strip()]
    (row,) = [r for r in rows if r["name"] == "granite-4.0-h-micro"]
    assert row["config"] == GRANITE_4_0_H_MICRO


def test_packed_rows_have_no_padding_and_weights_stop_at_boundaries():
    docs = [np.arange(1, 6), np.arange(10, 13), np.arange(20, 40)]
    out = pack_documents(docs, 8, eos_id=0)
    np.testing.assert_array_equal(
        out["tokens"], [[1, 2, 3, 4, 5, 0, 10, 11], [12, 0, 20, 21, 22, 23, 24, 25],
                        [26, 27, 28, 29, 30, 31, 32, 33]])
    np.testing.assert_array_equal(
        out["segment_ids"], [[0] * 6 + [1] * 2, [0] * 2 + [1] * 6, [0] * 8])
    np.testing.assert_array_equal(
        out["loss_weights"], [[1, 1, 1, 1, 1, 0, 1, 0], [1, 0, 1, 1, 1, 1, 1, 0],
                              [1] * 7 + [0]])
    np.testing.assert_array_equal(out["targets"][:, :-1], out["tokens"][:, 1:])


def test_flops_and_bytes_counted_from_shapes():
    from benchmark import flops_lm

    with open(os.path.join(ROOT, "benchmark", "configs",
                           "granite-4.0-h-micro.json")) as f:
        cell = json.load(f)
    mamba = 2048 * 8512 + 4096 * 2048
    attention = 2 * 2048 * 2048 + 2 * 2048 * 512
    mlp = 2048 * 16384 + 8192 * 2048
    assert flops_lm.mamba_matmul_macs(cell) == mamba
    assert flops_lm.attention_matmul_macs(cell) == attention
    matmul = 9 * mamba + attention + 10 * mlp + 12544 * 2048
    assert flops_lm.matmul_macs_per_token(cell) == matmul == cell["matmul_macs_per_token"]
    assert flops_lm.scan_macs_per_token(cell) == 9 * 3 * 4096 * 128
    assert cell["train_flops_per_image"] == flops_lm.train_flops_per_sequence(
        cell) == 6 * 4096 * (matmul + 9 * 3 * 4096 * 128)
    assert flops_lm.scan_train_flops(cell, 4096) == 6 * 4096 * 9 * 3 * 4096 * 128
    forward = 2 * (4096 + 128 + 128) + 4 * 64 + 4 * 4096   # x, B, C; dt; y
    assert flops_lm.scan_train_bytes(cell, 4096) == 2 * forward * 4096 * 9
    least, bound = flops_lm.scan_roofline_seconds(
        cell, 4096, {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
    assert bound == "bytes" and 2.0e-3 < least < 2.5e-3


def _old_decay_rule(path, x):
    leaf = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
    return leaf not in ("bias", "scale")


def test_decay_mask_leaves_out_every_vector():
    from deep_vision_tpu.core.optim import _weight_decay_mask

    batch = small_rows()
    params = jax.eval_shape(
        lambda: small_model().init(jax.random.PRNGKey(0), batch["tokens"],
                                   batch["segment_ids"]))["params"]
    mask = flat(_weight_decay_mask(params))
    decayed = {k.rsplit("/", 1)[-1] for k, v in mask.items() if v}
    spared = {k.rsplit("/", 1)[-1] for k, v in mask.items() if not v}
    assert decayed == {"kernel", "embedding", "conv_kernel"}
    assert spared == {"scale", "A_log", "D", "dt_bias", "conv_bias"}


@pytest.mark.parametrize("name", ["resnet50", "yolov3_coco"])
def test_decay_mask_of_the_benchmarks_image_models_is_what_it_was(name):
    from deep_vision_tpu.core.config import get_config
    from deep_vision_tpu.core.optim import _weight_decay_mask

    model = get_config(name).model()
    variables = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 64, 64, 3), jnp.float32), train=False))
    params = variables["params"]
    old = jax.tree_util.tree_map_with_path(_old_decay_rule, params)
    assert flat(_weight_decay_mask(params)) == flat(old)


def test_prefetcher_counts_tokens_and_documents(mesh1):
    from deep_vision_tpu.data.pipeline import DevicePrefetcher
    from deep_vision_tpu.tasks.language_modeling import LanguageModelingTask

    batch = small_rows(rows=2)
    docs = int((batch["segment_ids"][:, -1] + 1).sum())
    with DevicePrefetcher(mesh1) as prefetcher:
        stream = prefetcher.iterate([batch] * 3,
                                    counters=LanguageModelingTask.batch_counters)
        assert len(list(stream)) == 3
        seg = batch["segment_ids"]
        pairs = int(((seg[:, :, None] == seg[:, None, :])
                     & np.tril(np.ones((LENGTH, LENGTH), bool))).sum())
        # rows of LENGTH are one face of the attention kernels' default block
        assert stream.stats()["counters"] == {"tokens": 3 * 2 * LENGTH,
                                              "documents": 3 * docs,
                                              "pairs": 3 * pairs,
                                              "attn_blocks": 3 * 2,
                                              "attn_blocks_causal": 3 * 2}


def _blocks_by_brute_force(seg, face=512):
    """Faces of ``face`` x ``face`` at or below the diagonal that hold a
    visible (query, key) pair, and all faces at or below it."""
    at = np.arange(seg.shape[1])
    n = seg.shape[1] // face
    visited = 0
    for row in seg:
        for qi in range(n):
            rows = slice(qi * face, (qi + 1) * face)
            for ki in range(qi + 1):
                cols = slice(ki * face, (ki + 1) * face)
                visited += bool(((row[rows, None] == row[None, cols])
                                 & (at[rows, None] >= at[None, cols])).any())
    return visited, len(seg) * n * (n + 1) // 2


@pytest.mark.parametrize("rows, want", [
    ("one_document", (136, 136)), ("every_token_its_own", (16, 136)),
    ("packed", None)])
def test_batch_counters_count_the_attention_kernels_blocks(rows, want):
    """Rows of 8,192 at the kernels' 512 x 512 faces: every causal face for
    one document, the diagonal's for documents of one token, and for packed
    documents what a brute-force look at every face gives."""
    from deep_vision_tpu.tasks.language_modeling import LanguageModelingTask

    if rows == "packed":
        rng = np.random.default_rng(11)
        seg = np.cumsum(rng.random((2, 8192)) < 1 / 600, axis=1).astype(np.int32)
        want = _blocks_by_brute_force(seg)
        assert 2 * 16 < want[0] < want[1] == 2 * 136
    else:
        seg = (np.zeros((1, 8192), np.int32) if rows == "one_document"
               else np.arange(8192, dtype=np.int32)[None])
    got = LanguageModelingTask.batch_counters({"segment_ids": seg})
    assert (got["attn_blocks"], got["attn_blocks_causal"]) == want
    assert got["tokens"] == seg.size


def test_batch_counters_leave_the_blocks_out_where_the_block_does_not_divide():
    from deep_vision_tpu.tasks.language_modeling import LanguageModelingTask

    got = LanguageModelingTask.batch_counters(
        {"segment_ids": np.zeros((1, 768), np.int32)})
    assert got == {"tokens": 768, "documents": 1, "pairs": 768 * 769 // 2}


def test_profiled_epoch_puts_the_counters_in_the_spans_header(tmp_path, mesh1):
    from deep_vision_tpu.core.config import get_config
    from deep_vision_tpu.core.trainer import Trainer
    from deep_vision_tpu.tasks.language_modeling import LanguageModelingTask

    cfg = get_config("granite_4_0_h_micro")
    cfg.extra["architecture"].update(SMALL)
    cfg.extra["sequence_length"] = LENGTH
    cfg.half_precision, cfg.batch_size, cfg.log_every_steps = False, 2, 2
    trainer = Trainer(cfg, cfg.model(), LanguageModelingTask(), mesh=mesh1,
                      workdir=str(tmp_path))
    batch = small_rows(rows=2)
    state = trainer.init_state(batch)
    trainer.profile_steps = (1, 3)
    state = trainer.train_epoch(state, [batch] * 4, trainer.start_epoch)
    assert int(state.step) == 4 and int(state.bad_steps) == 0
    with open(tmp_path / "spans.jsonl") as f:
        header = json.loads(f.readline())
    assert header["tokens"] == 4 * 2 * LENGTH and header["batches"] == 4
    assert header["documents"] == 4 * int((batch["segment_ids"][:, -1] + 1).sum())
    series = {}
    with open(tmp_path / "metrics.jsonl") as f:
        for line in f:
            row = json.loads(line)
            series[row["name"]] = row["value"]
    assert series["input_tokens_per_step"] == 2 * LENGTH
    assert np.isfinite(series["train_loss"]) and "train_token_accuracy" in series


def test_cli_trains_three_steps_at_the_test_size(tmp_path, capsys):
    from deep_vision_tpu.cli import train

    overrides = [f"{k}={json.dumps(v)}" for k, v in SMALL.items()
                 if GRANITE_4_0_H_MICRO[k] != v]
    overrides.append(f"sequence_length={LENGTH}")
    argv = ["-m", "granite_4_0_h_micro", "--synthetic", "--synthetic-size", "3",
            "--epochs", "1", "--mesh", "data=1", "--workdir", str(tmp_path)]
    for item in overrides:
        argv += ["--override", item]
    assert train.main(argv) == 0
    out = capsys.readouterr().out
    assert "final:" in out and "token_accuracy" in out
    steps = set()
    with open(tmp_path / "metrics.jsonl") as f:
        for line in f:
            row = json.loads(line)
            if row["name"] == "train_loss":
                steps.add(row["step"])
                assert np.isfinite(row["value"])
    assert max(steps) == 3
    with pytest.raises(SystemExit):
        train.main(argv + ["--override", "no_such_key=1"])
