"""The latent-attention decoder with routed and shared experts and a
multi-token-prediction module at a small size on the CPU: hidden 64, 4
heads of 12 + 4 (values of 16) through latents of 24 and 16, dense width 96,
16 experts of width 48 with four a token beside a shared one, rows of 64,
three layers of which the first is dense, one module, 128 rows of
vocabulary.  The model against the benchmark's plain reference, the shares
of a layer against the whole, and what ties a packed row's documents apart."""

import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deep_vision_tpu.data.text import pack_documents, synthetic_corpus
from deep_vision_tpu.models.glm4_moe_lite import (
    Glm4MoeLite,
    Glm4MoeLiteConfig,
    next_in_document,
)
from deep_vision_tpu.tasks.language_modeling import (
    LanguageModelingTask,
    second_targets,
)
from deep_vision_tpu.zoo.language import GLM_4_7_FLASH

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = os.path.join(ROOT, "benchmark", "configs", "GLM-4.7-Flash.json")
SMALL = dict(GLM_4_7_FLASH, hidden_size=64, intermediate_size=96,
             moe_intermediate_size=48, num_attention_heads=4,
             num_key_value_heads=4, q_lora_rank=24, kv_lora_rank=16,
             qk_nope_head_dim=12, qk_rope_head_dim=4, v_head_dim=16,
             n_routed_experts=16, num_hidden_layers=3, vocab_size=128)
LENGTH = 64
MTP_WEIGHT = 0.3
SHARES = [(first, 2) for first in range(0, 16, 2)]
OTHER_MODELS = {"mamba", "attention", "mlp", "moe", "conv_op", "gqa_op",
                "dense_ffn", "ssd"}


def small_rows(seed=3, rows=2):
    docs = synthetic_corpus(LENGTH * (rows + 1), SMALL["vocab_size"], seed=seed,
                            median_length=11, sigma=0.6, max_length=LENGTH)
    batch = pack_documents(docs, LENGTH)
    assert len(np.flatnonzero(np.diff(batch["segment_ids"][0]))) >= 2
    return {k: v[:rows] for k, v in batch.items()}


def small_model(first=0, count=None):
    return Glm4MoeLite(Glm4MoeLiteConfig.from_dict(SMALL, first, count),
                       attention_block=16, dtype=jnp.float32)


def flat(tree):
    from flax import traverse_util

    return traverse_util.flatten_dict(dict(tree), sep="/")


def unflat(leaves):
    from flax import traverse_util

    return traverse_util.unflatten_dict(leaves, sep="/")


def seeded_params(model, batch, seed=5, bias=0.03):
    """The model's own init, and its selection biases (state beside the
    parameters) drawn away from zero."""
    tokens, seg = jnp.asarray(batch["tokens"]), jnp.asarray(batch["segment_ids"])
    variables = model.init(jax.random.PRNGKey(seed), tokens, seg)
    assert set(variables) == {"params", "batch_stats"}
    biases = flat(variables["batch_stats"])
    assert all(name.endswith("expert_bias") for name in biases)
    for i, name in enumerate(sorted({**flat(variables["params"]), **biases})):
        if name in biases:
            biases[name] = jax.random.uniform(
                jax.random.fold_in(jax.random.PRNGKey(seed + 1), i),
                biases[name].shape, minval=-bias, maxval=bias)
    return variables["params"], unflat(biases)


@pytest.fixture(scope="module")
def module():
    from benchmark.byname import load_module

    return load_module(os.path.join(ROOT, "benchmark", "configs",
                                    "GLM-4.7-Flash.py"), "glm_ref")


def reference_of(module, first=0, count=None):
    held = SMALL["n_routed_experts"] - first if count is None else count
    return module.Reference(dict(
        SMALL, n_routed_experts=held, expert_first=first,
        mtp_loss_weight=MTP_WEIGHT,
        published={"n_routed_experts": SMALL["n_routed_experts"]}))


def relative(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.linalg.norm(got - want) / max(np.linalg.norm(want), 1e-30)


# ------------------------------------------------------------- the shares

def test_the_eight_shares_of_a_layer_add_up_to_the_whole_reference(module):
    """Each chip's routed block (its experts' terms and the shared expert,
    which every chip computes whole), summed over the eight chips that share
    the layer with the shared expert counted once, is the uncut reference's
    block: nothing counted twice, nothing left out."""
    from deep_vision_tpu.models.glm4_moe_lite import RoutedFeedForward, SwiGLU

    whole_cfg = Glm4MoeLiteConfig.from_dict(SMALL)
    u = jax.random.normal(jax.random.PRNGKey(11), (1, 96, whole_cfg.hidden_size))
    whole = RoutedFeedForward(whole_cfg, jnp.float32)
    variables = whole.init(jax.random.PRNGKey(12), u)
    p = {k: 10 * v for k, v in flat(variables["params"]).items()}
    bias = jax.random.uniform(jax.random.PRNGKey(13), (16,), minval=-0.2, maxval=0.2)
    want, counters = reference_of(module)._routed(
        {**p, "expert_bias": bias}, u[0], "float32", module.FAULTS)
    assert counters["assignments"] == 96 * 4
    shared = SwiGLU(whole_cfg.moe_intermediate_size, whole_cfg.hidden_size,
                    jnp.float32).apply({"params": unflat(
                        {k[len("shared/"):]: v for k, v in p.items()
                         if k.startswith("shared/")})}, u)
    assert float(jnp.linalg.norm(shared)) > 0.1 * float(jnp.linalg.norm(want))
    total, assignments = jnp.zeros_like(u), 0
    for first, count in SHARES:
        cfg = Glm4MoeLiteConfig.from_dict(SMALL, first, count)
        mine = {k: v[first:first + count] if k.startswith("experts_w") else v
                for k, v in p.items()}
        part, got = RoutedFeedForward(cfg, jnp.float32).apply(
            {"params": unflat(mine), "batch_stats": {"expert_bias": bias}}, u)
        theirs, _ = reference_of(module, first, count)._routed(
            {**mine, "expert_bias": bias}, u[0], "float32", module.FAULTS)
        np.testing.assert_allclose(part[0], theirs, rtol=1e-4, atol=1e-5)
        assert float(jnp.linalg.norm(part - shared)) > 0 and got["dropped"] == 0
        total, assignments = total + part, assignments + int(got["assignments"])
    np.testing.assert_allclose((total - 7 * shared)[0], want, rtol=1e-4, atol=2e-5)
    assert assignments == 96 * 4


# -------------------------------------------------------------- the model

@pytest.mark.parametrize("first, count", [(0, None), (4, 2), (8, 8)],
                         ids=["all_experts", "an_eighth", "the_upper_half"])
def test_logits_losses_and_every_gradient_match_the_reference(module, first, count):
    batch = small_rows()
    model = small_model(first, count)
    params, biases = seeded_params(model, batch)
    ref = reference_of(module, first, count)
    jbatch = {k: jnp.asarray(v) for k, v in batch.items()}
    tokens, seg = jbatch["tokens"], jbatch["segment_ids"]
    got, got2, counters = model.apply({"params": params, "batch_stats": biases},
                                      tokens, seg)
    leaves = {**flat(params), **flat(biases)}
    want, want2 = ref.logits(leaves, tokens, seg)
    _, _, theirs = ref.forward(leaves, tokens, seg)
    assert float(jnp.std(want)) > 0.01 and float(jnp.std(want2)) > 0.01
    np.testing.assert_allclose(got, want, rtol=1e-3, atol=1e-4)
    np.testing.assert_allclose(got2, want2, rtol=1e-3, atol=1e-4)
    assert float(jnp.abs(want - want2).max()) > 0.01
    assert theirs["assignments"].shape == (2, 3)     # two layers and the module
    assert counters["moe_assignments"] == float(theirs["assignments"].sum())
    np.testing.assert_allclose(counters["moe_bias_lift"],
                               theirs["bias_lift"].mean(), rtol=1e-4)
    assert counters["moe_dropped"] == 0
    if count is None:
        assert counters["moe_assignments"] == 2 * 3 * LENGTH * 4
        assert counters["moe_unrouted_tokens"] == 0
    else:
        assert 0 < counters["moe_unrouted_tokens"] < 2 * LENGTH

    task = LanguageModelingTask(MTP_WEIGHT)

    def loss(p):
        return task.loss(
            model.apply({"params": p, "batch_stats": biases}, tokens, seg), jbatch)

    (got_loss, metrics), got_grads = jax.value_and_grad(loss, has_aux=True)(params)
    (want_loss, aux), want_grads = jax.value_and_grad(ref.loss, has_aux=True)(
        leaves, jbatch)
    assert abs(float(got_loss) - float(want_loss)) < 1e-5 * float(want_loss)
    assert abs(float(metrics["mtp_loss"]) - float(aux["mtp_loss"])) < 1e-5 * float(
        aux["mtp_loss"])
    assert abs(float(got_loss) - float(aux["loss"] + MTP_WEIGHT * aux["mtp_loss"])
               ) < 1e-5 * float(want_loss)
    assert 0 < float(metrics["mtp_targets"]) == float(aux["mtp_targets"]) < float(
        jbatch["loss_weights"].sum())
    got_grads = flat(got_grads)
    assert got_grads.keys() == want_grads.keys() - flat(biases).keys()
    for leaf, want_leaf in want_grads.items():
        if leaf.endswith("expert_bias"):     # no parameter, and no gradient
            assert not np.asarray(want_leaf).any()
        else:
            assert float(jnp.linalg.norm(want_leaf)) > 0, leaf
            assert relative(got_grads[leaf], want_leaf) < 1e-3, leaf


FAULTS = [dict(rotate="all"), dict(rope_key="per_head"), dict(latent_norm=False),
          dict(shared=False), dict(scale=1.0), dict(second="across"),
          dict(mtp_weight=0.0)]


@pytest.mark.parametrize("fault", FAULTS, ids=lambda f: "-".join(map(str, *f.items())))
def test_every_planted_fault_of_the_reference_moves_the_loss_or_a_gradient(
        module, fault):
    batch = {k: jnp.asarray(v) for k, v in small_rows().items()}
    model = small_model(4, 4)
    params, biases = seeded_params(model, batch)
    leaves = {**flat(params), **flat(biases)}
    ref = reference_of(module, 4, 4)
    grad = jax.value_and_grad(ref.loss, has_aux=True)
    (right, _), right_grads = grad(leaves, batch)
    (wrong, _), wrong_grads = grad(leaves, batch, **fault)
    moved = max(relative(wrong_grads[k], right_grads[k]) for k in right_grads
                if not k.endswith("expert_bias"))
    assert abs(float(wrong) - float(right)) > 1e-4 * float(right) or moved > 0.01
    assert moved > 0.01


def test_second_targets_stop_at_a_documents_end_and_at_the_rows():
    """``w2`` is 0 on the two positions before a boundary and on a row's
    last two; elsewhere the second target is the token two on."""
    a = 23
    tokens = np.arange(1, LENGTH + 1, dtype=np.int32)[None]
    seg = np.concatenate([np.zeros(a, np.int32), np.ones(LENGTH - a, np.int32)])[None]
    w = np.ones((1, LENGTH), np.float32)
    w[0, a - 1] = w[0, -1] = 0.0
    targets = np.concatenate([tokens[:, 1:], tokens[:, -1:]], axis=1)
    targets2, w2 = second_targets(jnp.asarray(targets), jnp.asarray(w))
    kept = np.flatnonzero(np.asarray(w2[0]))
    assert sorted(set(range(LENGTH)) - set(kept)) == [a - 2, a - 1, LENGTH - 2,
                                                      LENGTH - 1]
    np.testing.assert_array_equal(np.asarray(targets2)[0, kept], tokens[0, kept + 2])
    after = np.asarray(next_in_document(jnp.asarray(tokens), jnp.asarray(seg)))[0]
    np.testing.assert_array_equal(after[kept], tokens[0, kept + 1])
    assert after[a - 1] == tokens[0, a - 1] and after[-1] == tokens[0, -1]


def test_a_row_of_two_documents_equals_the_two_run_apart():
    """Rotary positions, the attention mask and the module's next token all
    start anew at a document's first token: the second document's logits
    (both sets) in a packed row are those of that document alone at the head
    of a row."""
    model = small_model(4, 4)
    rng = np.random.default_rng(0)
    a, b = 23, LENGTH - 23     # a boundary off every block's edge
    tokens = rng.integers(1, SMALL["vocab_size"], (1, LENGTH)).astype(np.int32)
    seg = np.concatenate([np.zeros(a, np.int32), np.ones(b, np.int32)])[None]
    params, biases = seeded_params(model, {"tokens": tokens, "segment_ids": seg})
    params = {"params": params, "batch_stats": biases}
    packed = model.apply(params, tokens, seg)[:2]
    alone = np.concatenate([tokens[:, a:], tokens[:, :a]], axis=1)
    alone_seg = np.concatenate([np.zeros(b, np.int32), np.ones(a, np.int32)])[None]
    apart = model.apply(params, alone, alone_seg)[:2]
    carried = model.apply(params, tokens, seg * 0)[:2]
    for together, single, one in zip(packed, apart, carried):
        np.testing.assert_allclose(together[:, a:], single[:, :b], rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(together[:, :a], single[:, b:], rtol=1e-4, atol=1e-5)
        # and they would differ if anything were carried across the boundary
        assert np.abs(np.asarray(one - together))[:, a:].max() > 1e-3


def test_gradients_equal_those_of_the_model_without_remat(monkeypatch):
    from deep_vision_tpu.models import glm4_moe_lite

    batch = small_rows()
    tokens, seg = jnp.asarray(batch["tokens"]), jnp.asarray(batch["segment_ids"])
    model = small_model(4, 4)
    params, biases = seeded_params(model, batch)

    def grads():
        def loss(p):
            a, b, _ = model.apply({"params": p, "batch_stats": biases}, tokens, seg)
            return jnp.sum(jnp.sin(a)) + jnp.sum(jnp.cos(b))
        return flat(jax.grad(loss)(params))

    got = grads()
    monkeypatch.setattr(glm4_moe_lite, "RematLayer", glm4_moe_lite.Glm4MoeLiteLayer)
    want = grads()
    for leaf, w in want.items():
        assert float(jnp.linalg.norm(got[leaf] - w)) <= 1e-6 * max(
            float(jnp.linalg.norm(w)), 1e-30), leaf


def test_latent_layer_launches_each_kernel_once_and_keeps_what_kept_names(
        jaxpr_equations, kept_between_passes):
    """A routed layer under ``RematLayer``: a gradient launches the
    attention's forward kernel once and its backward kernel once, and what is
    kept between the passes beside parameters, constants, the layer's input
    and this test's own cosine is what ``KEPT`` names, in the order computed."""
    from deep_vision_tpu.models import glm4_moe_lite

    cfg = Glm4MoeLiteConfig.from_dict(SMALL, 4, 4)
    seg = jnp.asarray(small_rows()["segment_ids"])
    h = jax.random.normal(jax.random.PRNGKey(7), seg.shape + (cfg.hidden_size,))
    positions = jnp.broadcast_to(jnp.arange(LENGTH), seg.shape)
    layer = glm4_moe_lite.RematLayer(cfg, True, 16, jnp.float32)
    variables = layer.init(jax.random.PRNGKey(8), h, seg, positions)

    def loss(params, h):
        return jnp.sum(jnp.sin(layer.apply(
            {**variables, "params": params}, h, seg, positions)[0]))

    params = variables["params"]
    jaxpr = jax.make_jaxpr(jax.grad(loss, (0, 1)))(params, h)
    kernels = [e.params["name"] for e in jaxpr_equations(jaxpr.jaxpr)
               if e.primitive.name == "pallas_call"]
    assert [k for k in kernels if k and k.startswith("causal_gqa")] == [
        "causal_gqa_fwd", "causal_gqa_bwd"]
    kept = kept_between_passes(loss, params, h)
    rows, heads = h.shape[:2], cfg.num_attention_heads
    wide = lambda n: ("f32", rows + (n,))  # noqa: E731
    named = [wide(cfg.q_lora_rank), wide(heads * cfg.head_dim),        # q_a, q_b
             wide(cfg.kv_lora_rank + cfg.qk_rope_head_dim),            # kv_a
             wide(heads * (cfg.qk_nope_head_dim + cfg.v_head_dim)),    # kv_b
             ("f32", rows + (heads, cfg.v_head_dim)),                  # the output
             ("f32", (rows[0], heads, rows[1])),                       # log-sum-exp
             wide(cfg.hidden_size)]                                    # o
    assert kept[:len(named)] == named
    rest = kept[len(named):]
    # the routing (integers of the chosen experts and of the sort), then the
    # shared expert's two products
    ints = [x for x in rest if x[0] == "i32"]
    assert ints and all(np.prod(shape) <= np.prod(rows) * cfg.num_experts_per_tok
                        for _, shape in ints)
    assert [x for x in rest if x[0] != "i32"] == [wide(cfg.moe_intermediate_size)] * 2


def op_names(lowered_text):
    return set(re.findall(r'loc\("([^"]*)"', lowered_text))


def test_scopes_name_the_parts_and_none_is_another_models():
    """The accepted benchmark's readers take an operation with a path
    component ``mamba``, ``attention`` or ``mlp`` for the granite model's and
    one with ``moe`` for the LFM2 model's: none of this model's scopes or
    module names is one of theirs."""
    batch = small_rows()
    tokens, seg = jnp.asarray(batch["tokens"]), jnp.asarray(batch["segment_ids"])
    model = small_model(4, 4)
    params, biases = seeded_params(model, batch)

    def loss(p):
        a, b, _ = model.apply({"params": p, "batch_stats": biases}, tokens, seg)
        return jnp.sum(a) + jnp.sum(b)

    text = jax.jit(jax.grad(loss)).lower(params).as_text(debug_info=True)
    names = op_names(text)
    parts = {part for name in names for part in name.split("/")}
    assert {"embed", "mla_op", "mla_core", "dense_block", "routed_ffn",
            "shared_expert", "moe_route", "moe_experts", "lm_head", "mtp"} <= parts
    assert not parts & OTHER_MODELS
    # ops/moe.py's switch: in every branch, forward, recomputed and backward,
    # the block's scopes are whole components under ``routed_ffn``
    branch = [name.split("/") for name in names if "feed_forward/cond/branch_" in name]
    assert branch and all("routed_ffn" in c and ("moe_route" in c or "moe_experts" in c)
                          for c in branch)
    assert {"buffer_256", "jvp(buffer_256)", "transpose(jvp(buffer_256))"} <= parts
    assert not {p for p in parts if re.search(r"\((moe_route|moe_experts)\)", p)}
    inside = {part for name in names if "mtp" in name.split("/")
              for part in name.split("/")}
    assert {"mla_op", "mla_core", "routed_ffn", "shared_expert", "lm_head",
            "embed"} <= inside and "dense_block" not in inside
    # the attention op and no weight product under mla_core
    core = [name for name in names if "mla_core" in name.split("/")]
    assert any("_forward" in name for name in core)
    assert any("_backward" in name for name in core)
    assert not any(name.endswith("dot_general") and "pallas_call" not in name
                   and "_forward" not in name and "_backward" not in name
                   for name in core)


# ---------------------------------------------------- zoo, files, counts

def count(shapes):
    return sum(int(np.prod(v.shape)) for v in jax.tree_util.tree_leaves(shapes))


def shapes_of(model):
    return jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), jnp.zeros((1, 256), jnp.int32),
                           jnp.zeros((1, 256), jnp.int32)))


def test_published_config_has_29_94_billion_parameters():
    shapes = shapes_of(Glm4MoeLite(Glm4MoeLiteConfig.from_dict(GLM_4_7_FLASH)))
    operator = 21_759_232
    routed = operator + 131_136 + 65 * 9_437_184 + 2 * 2048
    assert routed == 635_311_424
    dense = operator + 3 * 2048 * 10240 + 2 * 2048
    module = 3 * 2048 + 4096 * 2048 + routed
    whole = count(shapes)          # the 47 x 64 selection biases among them
    assert whole == 46 * routed + dense + 2048 + 2 * 154880 * 2048 + module
    assert whole - module == 29_943_393_920
    assert count(shapes["batch_stats"]) == 47 * 64


def cell_model():
    with open(CELL) as f:
        cell = json.load(f)
    arch = dict(cell, n_routed_experts=cell["published"]["n_routed_experts"])
    return cell, Glm4MoeLite(Glm4MoeLiteConfig.from_dict(
        arch, cell["expert_first"], cell["n_routed_experts"]))


def test_the_cells_share_holds_706_million_parameters():
    _, model = cell_model()
    assert count(shapes_of(model)) == 706_518_848


def test_benchmark_file_differs_from_the_published_config_only_where_it_says():
    cell, _ = cell_model()
    assert cell["reduced"] == ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    differs = [k for k, v in GLM_4_7_FLASH.items() if cell[k] != v]
    assert sorted(differs) == sorted(cell["reduced"])
    assert cell["published"] == {k: GLM_4_7_FLASH[k] for k in cell["reduced"]}
    assert (cell["num_hidden_layers"], cell["n_routed_experts"],
            cell["expert_first"], cell["first_k_dense_replace"]) == (5, 8, 0, 1)
    assert cell["vocab_size"] * 8 == 154880
    assert (cell["sequence_length"], cell["batch_size"], cell["mtp_loss_weight"],
            cell["expert_bias_update_rate"]) == (8192, 1, 0.3, 0.03)
    for name in ("source", "deployment"):
        assert isinstance(cell[name], str) and cell[name]
    assert len(cell["source"]) <= 200


def test_zoo_holds_the_catalogs_config_key_for_key():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("the catalog of public architectures is not on this machine")
    with open(catalog) as f:
        rows = [json.loads(line) for line in f if line.strip()]
    (row,) = [r for r in rows if r["name"] == "GLM-4.7-Flash"]
    assert row["config"] == GLM_4_7_FLASH


def test_flops_counted_from_shapes():
    from benchmark import flops_mla

    cell, _ = cell_model()
    operator = 2048 * 768 + 768 * 5120 + 2048 * 576 + 512 * 8960 + 5120 * 2048
    assert flops_mla.operator_macs(cell) == operator == 21_757_952
    routed = operator + 2048 * 64 + 9_437_184 + 0.5 * 9_437_184
    assert flops_mla.routed_layer_macs(cell) == routed == 36_044_800
    total = (operator + 3 * 2048 * 10240 + 5 * routed + 4096 * 2048
             + 2 * 19360 * 2048)
    assert flops_mla.matmul_macs_per_token(cell) == total == cell[
        "matmul_macs_per_token"] == 352_583_680
    assert cell["train_flops_per_image"] == flops_mla.train_flops_per_sequence(
        cell) == 6 * 8192 * total == 17_330_193_039_360
    # attention's needed work follows the pairs a document mask leaves visible
    pairs = 8192 * 8193 // 2
    assert flops_mla.core_train_flops(cell, pairs) == 6 * 2 * pairs * 256 * 20 * 6
    assert flops_mla.core_train_bytes(cell) == 8 * 8192 * 5120 * 2 * 6
    least, bound = flops_mla.core_roofline_seconds(
        cell, pairs, {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
    assert bound == "flops" and abs(least - 6 * 2 * pairs * 256 * 120 / 197e12) < 1e-9


# ------------------------------------------------------ optimizer, trainer

def test_decay_mask_leaves_out_every_norm_and_the_bias_is_no_parameter():
    from deep_vision_tpu.core.optim import _weight_decay_mask

    batch = small_rows()
    variables = jax.eval_shape(
        lambda: small_model(4, 4).init(jax.random.PRNGKey(0), batch["tokens"],
                                       batch["segment_ids"]))
    assert sorted(flat(variables["batch_stats"])) == [
        "layer_1/feed_forward/expert_bias", "layer_2/feed_forward/expert_bias",
        "mtp/layer/feed_forward/expert_bias"]
    params = variables["params"]
    assert not any(k.endswith("expert_bias") for k in flat(params))
    mask = flat(_weight_decay_mask(params))
    decayed = {k.rsplit("/", 1)[-1] for k, v in mask.items() if v}
    spared = {"/".join(k.rsplit("/", 2)[-2:]) for k, v in mask.items() if not v}
    assert decayed == {"kernel", "embedding", "router", "experts_w1",
                       "experts_w3", "experts_w2"}
    assert spared == {"q_a_norm/scale", "kv_a_norm/scale", "operator_norm/scale",
                      "ffn_norm/scale", "final_norm/scale", "enorm/scale",
                      "hnorm/scale"}
    # every leaf has a rule in the benchmark's weights
    from benchmark import weights_moe

    for leaf in {**flat(params), **flat(variables["batch_stats"])}:
        weights_moe.kind_of(leaf)


def trainer_at_the_test_size(tmp_path, mesh1, first=4, count=4):
    from deep_vision_tpu.core.config import get_config
    from deep_vision_tpu.core.trainer import Trainer

    cfg = get_config("glm_4_7_flash")
    cfg.extra["architecture"].update(SMALL)
    cfg.extra.update(sequence_length=LENGTH, expert_first=first, expert_count=count)
    cfg.half_precision, cfg.batch_size, cfg.log_every_steps = False, 2, 1
    return Trainer(cfg, cfg.model(),
                   LanguageModelingTask(cfg.extra["mtp_loss_weight"]), mesh=mesh1,
                   workdir=str(tmp_path))


def test_three_steps_through_train_epoch_log_the_counters(tmp_path, mesh1):
    trainer = trainer_at_the_test_size(tmp_path, mesh1)
    batch = small_rows(rows=2)
    state = trainer.init_state(batch)
    biases = flat(state.batch_stats)
    assert len(biases) == 3 and all(k.endswith("expert_bias") for k in biases)
    before = jax.device_get({**biases, **{k: flat(state.params)[k] for k in (
        "mtp/eh_proj/kernel", "layer_1/operator/kv_b/kernel", "lm_head/kernel")}})
    state = trainer.train_epoch(state, [batch] * 3, trainer.start_epoch)
    assert int(state.step) == 3 and int(state.bad_steps) == 0
    after = jax.device_get({**flat(state.params), **flat(state.batch_stats)})
    rate = trainer.config.extra["expert_bias_update_rate"]
    assert rate == 3e-2
    for name in biases:      # a step moves a bias by the rate, up or down
        moved = (after[name] - before[name]) / rate
        np.testing.assert_allclose(np.abs(moved).round(), np.abs(moved), atol=1e-3)
        assert (moved > 0).any() and (moved < 0).any()
    for name in ("mtp/eh_proj/kernel", "layer_1/operator/kv_b/kernel",
                 "lm_head/kernel"):
        assert np.abs(after[name] - before[name]).max() > 0
    series = {}
    with open(tmp_path / "metrics.jsonl") as f:
        for line in f:
            row = json.loads(line)
            series.setdefault(row["name"], []).append(row["value"])
    assert len(series["train_loss"]) == 3 and np.isfinite(series["train_loss"]).all()
    assert series["train_moe_dropped"] == [0.0] * 3
    assert all(0 < v <= 2 * 3 * LENGTH * 4 for v in series["train_moe_assignments"])
    # three routed blocks a step, each in buffers of 256 or of all 512 rows
    assert all(held <= rows and rows in {768, 1024, 1280, 1536}
               for rows, held in zip(series["train_moe_buffer_rows"],
                                     series["train_moe_assignments"]))
    _, w2 = second_targets(jnp.asarray(batch["targets"]),
                           jnp.asarray(batch["loss_weights"]))
    assert series["train_mtp_targets"] == [float(w2.sum())] * 3
    assert all(0 < v < s for v, s in zip(series["train_mtp_loss"],
                                         np.asarray(series["train_loss"]) / 0.3))
    assert "train_token_accuracy" in series
    want = LanguageModelingTask.batch_counters(batch)
    assert series["input_pairs_per_step"] == [float(want["pairs"])]
    assert series["input_attn_blocks_per_step"] == [2.0]
    assert series["input_attn_blocks_causal_per_step"] == [2.0]
    assert series["input_tokens_per_step"] == [float(2 * LENGTH)]


def test_pairs_counts_what_a_causal_document_mask_leaves_visible():
    batch = small_rows(rows=2)
    seg = batch["segment_ids"]
    visible = (seg[:, :, None] == seg[:, None, :]) & np.tril(
        np.ones((LENGTH, LENGTH), bool))
    got = LanguageModelingTask.batch_counters(batch)
    assert got["pairs"] == int(visible.sum())
    assert got["tokens"] == 2 * LENGTH
    # a row of LENGTH is one face of the attention kernels' default block
    assert (got["attn_blocks"], got["attn_blocks_causal"]) == (2, 2)
    one = LanguageModelingTask.batch_counters(
        {"segment_ids": np.zeros((1, LENGTH), np.int32)})
    assert one["pairs"] == LENGTH * (LENGTH + 1) // 2 and one["documents"] == 1


def test_attention_blocks_reach_the_input_series_beside_pairs():
    """Rows of 1,024, two faces of 512 a side: a row whose second half is
    another document leaves the face below the diagonal unvisited."""
    seg = np.zeros((2, 1024), np.int32)
    seg[1, 512:] = 1
    got = LanguageModelingTask.batch_counters({"segment_ids": seg})
    visible = (seg[:, :, None] == seg[:, None, :]) & np.tril(
        np.ones((1024, 1024), bool))
    faces = visible.reshape(2, 2, 512, 2, 512).any(axis=(2, 4))
    assert faces.sum() == 5
    assert got == {"tokens": 2048, "documents": 3, "pairs": int(visible.sum()),
                   "attn_blocks": 5, "attn_blocks_causal": 6}


def test_cli_trains_three_steps_at_the_test_size(tmp_path, capsys):
    from deep_vision_tpu.cli import train

    overrides = [f"{k}={json.dumps(v)}" for k, v in SMALL.items()
                 if GLM_4_7_FLASH[k] != v]
    overrides += [f"sequence_length={LENGTH}", "expert_first=4", "expert_count=4"]
    argv = ["-m", "glm_4_7_flash", "--synthetic", "--synthetic-size", "3",
            "--epochs", "1", "--mesh", "data=1", "--workdir", str(tmp_path)]
    for item in overrides:
        argv += ["--override", item]
    assert train.main(argv) == 0
    out = capsys.readouterr().out
    assert "final:" in out and "token_accuracy" in out
    steps, names = set(), set()
    with open(tmp_path / "metrics.jsonl") as f:
        for line in f:
            row = json.loads(line)
            names.add(row["name"])
            if row["name"] == "train_loss":
                steps.add(row["step"])
                assert np.isfinite(row["value"])
    assert max(steps) == 3 and {"train_moe_assignments", "train_mtp_loss",
                                "train_mtp_targets"} <= names


def test_cli_builds_the_published_models_config():
    from deep_vision_tpu.core.config import get_config

    cfg = get_config("glm_4_7_flash")
    model = cfg.model()
    assert model.cfg.n_routed_experts == model.cfg.held == 64
    assert model.cfg.num_hidden_layers == 47 and model.cfg.vocab_size == 154880
    assert (model.cfg.head_dim, model.cfg.v_head_dim) == (256, 256)
    assert cfg.extra["sequence_length"] == 8192
    assert cfg.extra["mtp_loss_weight"] == 0.3
    for wrong in (dict(n_group=2), dict(rope_scaling={"type": "yarn"}),
                  dict(attention_bias=True), dict(norm_topk_prob=False),
                  dict(partial_rotary_factor=0.5), dict(num_nextn_predict_layers=2),
                  dict(v_head_dim=128)):
        with pytest.raises(ValueError):
            Glm4MoeLiteConfig.from_dict(dict(GLM_4_7_FLASH, **wrong))
    with pytest.raises(ValueError):
        Glm4MoeLiteConfig.from_dict(GLM_4_7_FLASH, 60, 8)
