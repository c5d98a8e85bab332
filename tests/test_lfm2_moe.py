"""The gated-conv / attention decoder with routed experts at a small size on
the CPU: hidden 64, 4 / 2 heads of 16, dense width 96, 16 experts of width
48 with four a token, rows of 64, three layers conv / attention / conv of
which the first is dense, 128 rows of vocabulary.  ``ops/moe.py`` against
its plain form, the model against the benchmark's plain reference, the
shares of a layer against the whole, and what ties a packed row's documents
apart."""

import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deep_vision_tpu.data.text import pack_documents, synthetic_corpus
from deep_vision_tpu.models.lfm2_moe import Lfm2Moe, Lfm2MoeConfig
from deep_vision_tpu.ops import moe
from deep_vision_tpu.zoo.language import LFM2_24B_A2B

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMALL = dict(LFM2_24B_A2B, hidden_size=64, intermediate_size=96,
             moe_intermediate_size=48, num_attention_heads=4,
             num_key_value_heads=2, num_experts=16,
             layer_types=["conv", "full_attention", "conv"],
             num_hidden_layers=3, num_dense_layers=1, vocab_size=128)
LENGTH = 64
SHARES = [(0, 4), (4, 4), (8, 4), (12, 4)]


def small_rows(seed=3, rows=2):
    docs = synthetic_corpus(LENGTH * (rows + 1), SMALL["vocab_size"], seed=seed,
                            median_length=11, sigma=0.6, max_length=LENGTH)
    batch = pack_documents(docs, LENGTH)
    assert len(np.flatnonzero(np.diff(batch["segment_ids"][0]))) >= 2
    return {k: v[:rows] for k, v in batch.items()}


def small_model(first=0, count=None, dtype=jnp.float32):
    return Lfm2Moe(Lfm2MoeConfig.from_dict(SMALL, first, count),
                   attention_block=16, dtype=dtype)


def flat(tree):
    from flax import traverse_util

    return traverse_util.flatten_dict(dict(tree), sep="/")


def unflat(leaves):
    from flax import traverse_util

    return traverse_util.unflatten_dict(leaves, sep="/")


def seeded_params(model, batch, seed=5, bias=0.2):
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
                                    "LFM2-24B-A2B.py"), "lfm2_ref")


def reference_of(module, first=0, count=None):
    held = SMALL["num_experts"] - first if count is None else count
    return module.Reference(dict(
        SMALL, num_experts=held, expert_first=first,
        published={"num_experts": SMALL["num_experts"]}))


def relative(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.linalg.norm(got - want) / max(np.linalg.norm(want), 1e-30)


# ------------------------------------------------------------------ route

def test_balancing_moves_every_bias_by_the_rate_against_its_load():
    """An expert over the mean load loses the rate, one under it gains it,
    one at the mean stays; absent experts count like held ones."""
    indices = jnp.asarray([[0, 1, 2, 3], [0, 1, 2, 4], [0, 1, 5, 6],
                           [0, 7, 5, 6]], jnp.int32)       # 16 over 8: mean 2
    bias = jnp.linspace(-0.1, 0.1, 8)
    got = np.asarray(moe.balanced_bias(bias, indices, 0.01) - bias)
    loads = np.bincount(np.asarray(indices).ravel(), minlength=8)
    np.testing.assert_array_equal(loads, [4, 3, 2, 1, 1, 2, 2, 1])
    np.testing.assert_allclose(got, 0.01 * np.sign(2 - loads), atol=1e-7)


def test_balancing_spreads_a_collapsed_router():
    """Every token chooses the same four experts; the balanced biases bring
    the fullest expert's load from all the tokens to near the mean."""
    u, router, _ = route_inputs(tokens=256)
    bias, worst = jnp.zeros(16).at[:4].set(1.0), []
    for _ in range(60):
        indices, _ = moe.route(u, router, bias, 4)
        worst.append(int(np.bincount(np.asarray(indices).ravel(), minlength=16).max()))
        bias = moe.balanced_bias(bias, indices, 0.05)
    assert worst[0] == 256 and worst[-1] < 1.5 * 256 * 4 / 16

def route_inputs(seed=0, tokens=48, hidden=32, experts=16):
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(keys[0], (tokens, hidden)),
            0.3 * jax.random.normal(keys[1], (hidden, experts)),
            jax.random.uniform(keys[2], (experts,), minval=-0.3, maxval=0.3))


def plain_route(u, router, bias, k):
    """Scores, the choice by score + bias, weights from the scores alone."""
    s = 1.0 / (1.0 + np.exp(-(np.asarray(u, np.float64) @ np.asarray(router, np.float64))))
    chosen = np.argsort(-(s + np.asarray(bias, np.float64)), axis=-1,
                        kind="stable")[:, :k]
    w = np.take_along_axis(s, chosen, -1)
    return chosen, w / (w.sum(-1, keepdims=True) + 1e-6)


def test_route_chooses_by_the_biased_scores_and_weighs_by_the_scores():
    u, router, bias = route_inputs()
    indices, weights = moe.route(u, router, bias, 4)
    want_indices, want_weights = plain_route(u, router, bias, 4)
    np.testing.assert_array_equal(indices, want_indices)
    np.testing.assert_allclose(weights, want_weights, rtol=1e-5)
    assert weights.dtype == jnp.float32 and indices.shape == (48, 4)
    # the bias changes the choice, and not the weights of what stays chosen
    plain_indices, _ = moe.route(u, router, jnp.zeros_like(bias), 4)
    assert (np.sort(indices, -1) != np.sort(plain_indices, -1)).any()
    np.testing.assert_allclose(weights.sum(-1), 1.0, atol=1e-5)


def test_route_with_scale_one_is_bit_for_bit_what_it_was():
    """``scale`` and ``eps`` came with a model whose router scales its
    weights; left at their defaults, or given as 1 and 1e-6, the weights are
    the bits of ``chosen / (sum + 1e-6)``, and a scale multiplies them."""
    u, router, bias = route_inputs()
    indices, weights = moe.route(u, router, bias, 4)
    scores = jax.nn.sigmoid(jnp.dot(u, router, precision=moe.HIGHEST))
    chosen = jnp.take_along_axis(scores, indices, axis=-1)
    was = chosen / (chosen.sum(-1, keepdims=True) + 1e-6)
    np.testing.assert_array_equal(weights, was)
    same_indices, same = moe.route(u, router, bias, 4, scale=1.0, eps=moe.NORM_EPS)
    np.testing.assert_array_equal(same_indices, indices)
    np.testing.assert_array_equal(same, was)
    scaled_indices, scaled = moe.route(u, router, bias, 4, scale=1.8, eps=1e-20)
    np.testing.assert_array_equal(scaled_indices, indices)
    np.testing.assert_allclose(scaled, 1.8 * chosen / chosen.sum(-1, keepdims=True),
                               rtol=1e-6)
    assert float(jnp.abs(scaled.sum(-1) - 1.8).max()) < 1e-5


def test_route_stays_float32_under_a_bfloat16_model():
    u, router, bias = route_inputs()
    indices, weights = moe.route(u.astype(jnp.bfloat16), router, bias, 4)
    want = plain_route(u.astype(jnp.bfloat16).astype(jnp.float32), router, bias, 4)
    np.testing.assert_array_equal(indices, want[0])
    np.testing.assert_allclose(weights, want[1], rtol=1e-5)


def test_gradient_reaches_the_router_through_the_weights_and_never_the_bias():
    u, router, bias = route_inputs()
    probe = jax.random.normal(jax.random.PRNGKey(4), (48, 4))

    def loss(u, router, bias):
        return jnp.sum(probe * moe.route(u, router, bias, 4)[1])

    def plain(u, router, bias):
        s = jax.nn.sigmoid(u @ router)
        w = jnp.take_along_axis(s, moe.route(u, router, bias, 4)[0], -1)
        return jnp.sum(probe * w / (w.sum(-1, keepdims=True) + 1e-6))

    got = jax.grad(loss, (0, 1, 2))(u, router, bias)
    want = jax.grad(plain, (0, 1))(u, router, bias)
    assert float(jnp.linalg.norm(want[1])) > 0
    np.testing.assert_allclose(got[0], want[0], rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(got[1], want[1], rtol=1e-4, atol=1e-6)
    assert not np.asarray(got[2]).any()


# --------------------------------------------------------- routed_experts

ROUTINGS = ["even", "skewed", "one_takes_all", "one_gets_none", "none_held"]
# 512 tokens x 4: buffers of 768 or of all 2,048 rows; the held rows of each
# case land inside a rung, on its edge, one past it, and fill the top
RUNG_TOKENS, RUNGS = 512, (768, 2048)
HELD_ROWS = [100, 768, 769, 1537, 2048]
CASES = ROUTINGS + [f"held_{rows}" for rows in HELD_ROWS]


def tokens_of(kind):
    return RUNG_TOKENS if kind.startswith("held_") else 40


def rung_of(rows):
    return next(c for c in RUNGS if c >= rows)


def routing(kind, experts=16, k=4, first=4, count=4, seed=0):
    """(T, k) experts a token, distinct within a token, and float32 weights."""
    rng = np.random.default_rng(seed)
    tokens = tokens_of(kind)
    if kind.startswith("held_"):     # exactly that many assignments are held
        rows = int(kind[5:])
        absent = np.array([e for e in range(experts)
                           if not first <= e < first + count])
        idx = np.stack([np.concatenate([
            rng.permutation(count)[:held] + first,
            rng.choice(absent, k - held, replace=False)])
            for held in rows // tokens + (np.arange(tokens) < rows % tokens)])
        idx = rng.permuted(idx, axis=1)
    elif kind == "even":
        idx = np.stack([(np.arange(k) * (experts // k) + t) % experts
                        for t in range(tokens)])
    elif kind == "skewed":
        p = np.arange(1, experts + 1, dtype=np.float64) ** -1.5
        p = np.roll(p / p.sum(), first)   # the held experts are the fullest
        idx = np.stack([rng.choice(experts, k, replace=False, p=p)
                        for _ in range(tokens)])
    elif kind == "one_takes_all":    # every token's every choice is held
        idx = np.stack([rng.permutation(count)[:k] + first for _ in range(tokens)])
        idx[:, 0] = first            # and one expert is in every token's four
        idx[:, 1:] = np.stack([rng.permutation(count - 1)[:k - 1] + first + 1
                               for _ in range(tokens)])
    elif kind == "one_gets_none":    # held expert first + 2 is never chosen
        pool = np.array([e for e in range(experts) if e != first + 2])
        idx = np.stack([rng.choice(pool, k, replace=False) for _ in range(tokens)])
    else:                            # nothing falls on the held experts
        pool = np.array([e for e in range(experts)
                         if not first <= e < first + count])
        idx = np.stack([rng.choice(pool, k, replace=False) for _ in range(tokens)])
    w = rng.uniform(0.1, 1.0, (tokens, k)).astype(np.float32)
    return jnp.asarray(idx, jnp.int32), jnp.asarray(w / w.sum(-1, keepdims=True))


def only_the_top_rung(monkeypatch):
    """The block as it was before the ladder: every buffer T x k rows."""
    monkeypatch.setattr(moe, "LADDER", ((1, 1),))


def test_the_ladder_is_fractions_of_the_worst_case_in_whole_row_tiles():
    assert moe._capacities(8192 * 4) == (12288, 32768)
    assert moe._capacities(RUNG_TOKENS * 4) == RUNGS
    assert moe._capacities(2 * LENGTH * 4) == (256, 512)      # the models' rows
    assert moe._capacities(40 * 4) == (160,)        # one rung: no switch at all
    assert all(c % moe.TILE[0] == 0 for c in moe._capacities(8192 * 4))


def expert_inputs(seed=1, tokens=40, hidden=32, width=24, count=4):
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(keys[0], (tokens, hidden)),
            0.2 * jax.random.normal(keys[1], (count, hidden, width)),
            0.2 * jax.random.normal(keys[2], (count, hidden, width)),
            0.2 * jax.random.normal(keys[3], (count, width, hidden)))


def plain_experts(u, indices, weights, w1, w3, w2, first):
    """Every held expert applied to every token, masked by the selection."""
    out = jnp.zeros_like(u)
    for e in range(w1.shape[0]):
        weight = jnp.sum(jnp.where(indices == first + e, weights, 0.0), -1)
        out = out + weight[:, None] * (
            (jax.nn.silu(u @ w1[e]) * (u @ w3[e])) @ w2[e])
    return out


@pytest.mark.parametrize("kind", CASES)
def test_routed_experts_match_every_expert_on_every_token(kind, monkeypatch):
    first, count = 4, 4
    indices, weights = routing(kind)
    u, w1, w3, w2 = expert_inputs(tokens=tokens_of(kind))
    got, counters = moe.routed_experts(u, indices, weights, w1, w3, w2, first)
    want = plain_experts(u, indices, weights, w1, w3, w2, first)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
    held = np.asarray((indices >= first) & (indices < first + count))
    loads = [int((np.asarray(indices) == first + e).sum()) for e in range(count)]
    assert counters["assignments"] == held.sum() == sum(loads)
    assert counters["max_load"] == max(loads)
    assert counters["unrouted_tokens"] == (~held.any(-1)).sum()
    assert counters["dropped"] == 0         # dropless, whatever the imbalance
    if kind.startswith("held_"):
        rows = int(kind[5:])
        assert counters["assignments"] == rows
        assert counters["buffer_rows"] == rung_of(rows)
        # the same block with every buffer at T x k rows, bit for bit
        only_the_top_rung(monkeypatch)
        top, theirs = moe.routed_experts(u, indices, weights, w1, w3, w2, first)
        assert theirs.pop("buffer_rows") == 4 * RUNG_TOKENS
        np.testing.assert_array_equal(got, top)
        assert theirs == {k: v for k, v in counters.items() if k != "buffer_rows"}
    else:
        assert counters["buffer_rows"] == 160
    if kind == "one_takes_all":
        assert max(loads) == 40 and counters["assignments"] == 40 * 4
    if kind == "one_gets_none":
        assert loads[2] == 0 and min(loads[:2] + loads[3:]) > 0
    if kind == "none_held":
        assert not np.asarray(got).any() and counters["unrouted_tokens"] == 40


@pytest.mark.parametrize("kind", CASES)
def test_routed_experts_gradients_match_the_plain_form(kind, monkeypatch):
    first = 4
    indices, weights = routing(kind, seed=2)
    args = expert_inputs(seed=3, tokens=tokens_of(kind))
    probe = jax.random.normal(jax.random.PRNGKey(8), args[0].shape)

    def loss(fn, u, weights, w1, w3, w2):
        out = fn(u, indices, weights, w1, w3, w2, first)
        return jnp.sum(probe * (out[0] if isinstance(out, tuple) else out))

    operands = (args[0], weights) + args[1:]
    got = jax.grad(lambda *a: loss(moe.routed_experts, *a), range(5))(*operands)
    want = jax.grad(lambda *a: loss(plain_experts, *a), range(5))(*operands)
    for g, w in zip(got, want):
        assert g.shape == w.shape and np.isfinite(np.asarray(g)).all()
        np.testing.assert_allclose(g, w, rtol=2e-4, atol=2e-5)
    if kind == "one_gets_none":
        assert not np.asarray(got[2][2]).any()    # no row, no gradient
    if kind.startswith("held_"):     # a lower rung's gradients are the top's,
        only_the_top_rung(monkeypatch)   # to float32's rounding of a row's sum
        top = jax.grad(lambda *a: loss(moe.routed_experts, *a), range(5))(*operands)
        for g, t in zip(got, top):
            np.testing.assert_allclose(g, t, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("kind", ["skewed", "held_300", "held_1000"])
def test_a_row_the_products_leave_out_is_counted_as_dropped(kind, monkeypatch):
    """``dropped`` is read off the grouped products' result: a program that
    cut a load short (a capacity) would leave rows out, and they count, in
    whichever rung's buffers."""
    u, w1, w3, w2 = expert_inputs(tokens=tokens_of(kind))
    indices, weights = routing(kind)
    product = moe._grouped_product

    def short(x, w, loads):       # the fullest expert's last three rows cut
        full = jnp.argmax(loads)
        end = jnp.cumsum(loads)[full]
        rows = jnp.arange(x.shape[0])[:, None]
        return jnp.where((rows >= end - 3) & (rows < end), 0, product(x, w, loads))

    _, sound = moe.routed_experts(u, indices, weights, w1, w3, w2, first=4)
    monkeypatch.setattr(moe, "_grouped_product", short)
    _, cut = moe.routed_experts(u, indices, weights, w1, w3, w2, first=4)
    assert sound["dropped"] == 0 and cut["dropped"] == 3
    assert cut["assignments"] == sound["assignments"]
    assert cut["buffer_rows"] == sound["buffer_rows"] == {
        "skewed": 160, "held_300": 768, "held_1000": 2048}[kind]


def test_routed_experts_in_bfloat16_are_the_float32_ones_within_rounding():
    indices, weights = routing("skewed")
    u, w1, w3, w2 = expert_inputs()
    got, _ = moe.routed_experts(u.astype(jnp.bfloat16), indices, weights,
                                w1, w3, w2, 4)
    want, _ = moe.routed_experts(u, indices, weights, w1, w3, w2, 4)
    assert got.dtype == jnp.bfloat16
    assert relative(got.astype(jnp.float32), want) < 2e-2


@pytest.mark.parametrize("kind, rungs", [("skewed", 1), ("held_600", 2)])
def test_no_scatter_of_rows_in_either_pass(jaxpr_equations, kind, rungs):
    """Rows move by gathers alone, forward and backward, in every branch of
    the switch: what is scattered outside the kernels is vectors (the
    inverse permutation, the kernels' tables of tiles and groups), never
    rows."""
    indices, weights = routing(kind)
    args = expert_inputs(tokens=tokens_of(kind))

    def loss(u, w1, w3, w2):
        return jnp.sum(moe.routed_experts(u, indices, weights, w1, w3, w2, 4)[0])

    jaxpr = jax.make_jaxpr(jax.grad(loss, (0, 1, 2, 3)))(*args)
    scatters = [e for e in jaxpr_equations(jaxpr.jaxpr, closed=("pallas_call",))
                if e.primitive.name.startswith("scatter")]
    assert scatters and all(e.outvars[0].aval.ndim == 1 for e in scatters)
    grouped = [e for e in jaxpr_equations(jaxpr.jaxpr, closed=("pallas_call",))
               if e.primitive.name == "pallas_call"]
    # a rung: forward; then in the backward branch its recomputation, dx, dW
    assert len(grouped) == 12 * rungs


def test_conditionals_hold_no_buffer_of_a_rung_and_none_lies_between_the_passes(
        jaxpr_equations, kept_between_passes):
    """One forward and one backward conditional a block.  What goes into one
    and comes out has the block's own shapes ((T, D), (T, k), the experts'
    leaves, vectors of T x k integers), never a rung's row buffer: the
    two-dimensional operands of a conditional, and its results, have T x k
    rows in all at most.  Between the passes lies nothing shaped like a
    buffer, of the rung that ran or of another: autodiff of the switch itself
    would keep zeros of every capacity's."""
    indices, weights = routing("held_600")
    u, w1, w3, w2 = expert_inputs(tokens=RUNG_TOKENS)
    worst = RUNG_TOKENS * 4

    def loss(u, weights, w1, w3, w2):
        return jnp.sum(jnp.sin(
            moe.routed_experts(u, indices, weights, w1, w3, w2, 4)[0]))

    jaxpr = jax.make_jaxpr(jax.grad(loss, range(5)))(u, weights, w1, w3, w2)
    conds = [e for e in jaxpr_equations(jaxpr.jaxpr, closed=("pallas_call", "cond"))
             if e.primitive.name == "cond"]
    assert [len(e.params["branches"]) for e in conds] == [2, 2]
    for eqn in conds:
        for side in (eqn.invars, eqn.outvars):
            rows = [v.aval.shape[0] for v in side if v.aval.ndim == 2]
            assert rows and sum(rows) <= worst, [v.aval for v in side]
    for dtype, shape in kept_between_passes(loss, u, weights, w1, w3, w2):
        assert len(shape) <= 1 or shape == weights.shape or (
            shape == u.shape and dtype == "f32"), (dtype, shape)   # the sine's


# ------------------------------------------------------ the shares add up

def test_the_four_shares_of_a_layer_add_up_to_the_whole_reference(module):
    """Each chip's part of a routed block, summed over the four chips that
    share the layer, is the uncut reference's routed block: nothing counted
    twice, nothing left out."""
    cfg = Lfm2MoeConfig.from_dict(SMALL)
    keys = jax.random.split(jax.random.PRNGKey(11), 6)
    hidden, width, experts = cfg.hidden_size, cfg.moe_intermediate_size, 16
    u = jax.random.normal(keys[0], (96, hidden))
    p = {"router": 0.3 * jax.random.normal(keys[1], (hidden, experts)),
         "expert_bias": jax.random.uniform(keys[2], (experts,), minval=-0.2,
                                           maxval=0.2),
         "experts_w1": 0.2 * jax.random.normal(keys[3], (experts, hidden, width)),
         "experts_w3": 0.2 * jax.random.normal(keys[4], (experts, hidden, width)),
         "experts_w2": 0.2 * jax.random.normal(keys[5], (experts, width, hidden))}
    whole, counters = reference_of(module)._routed(
        p, u, "float32", "scores", "chosen", None)
    assert counters["assignments"] == 96 * 4
    indices, weights = moe.route(u, p["router"], p["expert_bias"], 4)
    total, assignments = jnp.zeros_like(u), 0
    for first, count in SHARES:
        held = slice(first, first + count)
        part, got = moe.routed_experts(
            u, indices, weights, p["experts_w1"][held], p["experts_w3"][held],
            p["experts_w2"][held], first)
        theirs, _ = reference_of(module, first, count)._routed(
            {**p, **{k: p[k][held] for k in ("experts_w1", "experts_w3", "experts_w2")}},
            u, "float32", "scores", "chosen", None)
        np.testing.assert_allclose(part, theirs, rtol=1e-4, atol=1e-5)
        assert float(jnp.linalg.norm(part)) > 0 and got["dropped"] == 0
        total, assignments = total + part, assignments + int(got["assignments"])
    np.testing.assert_allclose(total, whole, rtol=1e-4, atol=1e-5)
    assert assignments == 96 * 4


# -------------------------------------------------------------- the model

@pytest.mark.parametrize("first, count", [(0, None), (4, 4), (8, 8)],
                         ids=["all_experts", "a_quarter", "the_upper_half"])
def test_logits_loss_and_every_gradient_match_the_reference(module, first, count):
    batch = small_rows()
    model = small_model(first, count)
    params, biases = seeded_params(model, batch)
    ref = reference_of(module, first, count)
    jbatch = {k: jnp.asarray(v) for k, v in batch.items()}
    tokens, seg = jbatch["tokens"], jbatch["segment_ids"]
    got, counters = model.apply({"params": params, "batch_stats": biases},
                                tokens, seg)
    leaves = {**flat(params), **flat(biases)}
    want, theirs = ref.forward(leaves, tokens, seg)
    assert float(jnp.std(want)) > 0.01
    np.testing.assert_allclose(got, want, rtol=1e-3, atol=1e-4)
    assert counters["moe_assignments"] == float(theirs["assignments"].sum())
    assert counters["moe_dropped"] == 0
    if count is None:
        assert counters["moe_assignments"] == 2 * 2 * LENGTH * 4
        assert counters["moe_unrouted_tokens"] == 0
    else:
        assert 0 < counters["moe_unrouted_tokens"] < 2 * LENGTH

    from deep_vision_tpu.tasks.language_modeling import LanguageModelingTask

    def loss(p):
        return LanguageModelingTask().loss(
            model.apply({"params": p, "batch_stats": biases}, tokens, seg),
            jbatch)[0]

    got_loss, got_grads = jax.value_and_grad(loss)(params)
    (want_loss, _), want_grads = jax.value_and_grad(ref.loss, has_aux=True)(
        leaves, jbatch)
    assert abs(float(got_loss) - float(want_loss)) < 1e-5 * float(want_loss)
    got_grads = flat(got_grads)
    assert got_grads.keys() == want_grads.keys() - flat(biases).keys()
    for leaf, want_leaf in want_grads.items():
        if leaf.endswith("expert_bias"):     # no parameter, and no gradient
            assert not np.asarray(want_leaf).any()
        else:
            assert float(jnp.linalg.norm(want_leaf)) > 0, leaf
            assert relative(got_grads[leaf], want_leaf) < 1e-3, leaf


def test_a_row_of_two_documents_equals_the_two_run_apart():
    """Conv taps, rotary positions and the attention mask all start anew at
    a document's first token: the second document's logits in a packed row
    are the logits of that document alone at the head of a row."""
    model = small_model(4, 4)
    rng = np.random.default_rng(0)
    a, b = 23, LENGTH - 23     # a boundary off every block's edge
    tokens = rng.integers(1, SMALL["vocab_size"], (1, LENGTH)).astype(np.int32)
    seg = np.concatenate([np.zeros(a, np.int32), np.ones(b, np.int32)])[None]
    params, biases = seeded_params(model, {"tokens": tokens, "segment_ids": seg})
    params = {"params": params, "batch_stats": biases}
    packed, _ = model.apply(params, tokens, seg)
    alone = np.concatenate([tokens[:, a:], tokens[:, :a]], axis=1)
    alone_seg = np.concatenate([np.zeros(b, np.int32), np.ones(a, np.int32)])[None]
    apart, _ = model.apply(params, alone, alone_seg)
    np.testing.assert_allclose(packed[:, a:], apart[:, :b], rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(packed[:, :a], apart[:, b:], rtol=1e-4, atol=1e-5)
    # and they would differ if anything were carried across the boundary
    carried, _ = model.apply(params, tokens, seg * 0)
    assert np.abs(np.asarray(carried - packed))[:, a:].max() > 1e-3


def test_gradients_equal_those_of_the_model_without_remat(monkeypatch):
    from deep_vision_tpu.models import lfm2_moe

    batch = small_rows()
    tokens, seg = jnp.asarray(batch["tokens"]), jnp.asarray(batch["segment_ids"])
    model = small_model(4, 4)
    params, biases = seeded_params(model, batch)

    def grads():
        return flat(jax.grad(lambda p: jnp.sum(jnp.sin(model.apply(
            {"params": p, "batch_stats": biases}, tokens, seg)[0])))(params))

    got = grads()
    monkeypatch.setattr(lfm2_moe, "RematLayer", lfm2_moe.Lfm2MoeLayer)
    want = grads()
    for leaf, w in want.items():
        assert float(jnp.linalg.norm(got[leaf] - w)) <= 1e-6 * max(
            float(jnp.linalg.norm(w)), 1e-30), leaf


def test_attention_layer_launches_each_kernel_once_and_keeps_what_kept_names(
        jaxpr_equations, kept_between_passes):
    """The ``full_attention`` layer with routed experts under ``RematLayer``:
    a gradient launches the attention's forward kernel once and its backward
    kernel once (the output and the log-sum-exp are kept, so the
    recomputation has no use for a second forward), and what is kept between
    the passes beside parameters, constants, the layer's input and this
    test's own cosine is what ``KEPT`` names, in the order computed."""
    from deep_vision_tpu.models import lfm2_moe

    cfg = Lfm2MoeConfig.from_dict(SMALL, 4, 4)
    seg = jnp.asarray(small_rows()["segment_ids"])
    h = jax.random.normal(jax.random.PRNGKey(7), seg.shape + (cfg.hidden_size,))
    positions = jnp.broadcast_to(jnp.arange(LENGTH), seg.shape)
    layer = lfm2_moe.RematLayer(cfg, "full_attention", True, 16, jnp.float32)
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
    rows, k = h.shape[:2], cfg.num_experts_per_tok
    hidden = ("f32", rows + (cfg.hidden_size,))
    kv = ("f32", rows + (cfg.num_key_value_heads * cfg.head_dim,))
    assert kept[:6] == [
        hidden, kv, kv,                                        # q, k, v
        ("f32", rows + (cfg.num_attention_heads, cfg.head_dim)),  # the output
        ("f32", (rows[0], cfg.num_attention_heads, rows[1])),  # log-sum-exp
        hidden]                                                # out_proj
    # the routing: integers of the chosen experts and of the sort
    assert kept[6:] and all(dtype == "i32" and np.prod(shape) <= np.prod(rows) * k
                            for dtype, shape in kept[6:])


def op_names(lowered_text):
    return set(re.findall(r'loc\("([^"]*)"', lowered_text))


def test_scopes_name_the_parts_and_none_is_another_models():
    """The accepted benchmark's reader takes any operation with a path
    component ``mamba``, ``attention`` or ``mlp`` for the granite model's:
    none of this model's scopes or module names is one of the three."""
    batch = small_rows()
    tokens, seg = jnp.asarray(batch["tokens"]), jnp.asarray(batch["segment_ids"])
    model = small_model(4, 4)
    params, biases = seeded_params(model, batch)
    text = jax.jit(jax.grad(lambda p: jnp.sum(model.apply(
        {"params": p, "batch_stats": biases}, tokens, seg)[0]))).lower(
            params).as_text(debug_info=True)
    parts = {part for name in op_names(text) for part in name.split("/")}
    assert {"embed", "conv_op", "gqa_op", "dense_ffn", "moe", "moe_route",
            "moe_experts", "lm_head"} <= parts
    assert not parts & {"mamba", "attention", "mlp", "ssd"}
    # inside the switch's branches, forward, recomputed and backward, the
    # block's two scopes stay whole components: a transform wraps the
    # branch's own ``buffer_<rows>`` and never them
    for rows in moe._capacities(2 * LENGTH * 4):
        assert {f"buffer_{rows}", f"jvp(buffer_{rows})",
                f"transpose(jvp(buffer_{rows}))"} <= parts
    assert not {p for p in parts if re.search(r"\((moe|moe_route|moe_experts)\)", p)}
    branch = [name.split("/") for name in op_names(text)
              if "feed_forward/cond/branch_" in name]
    assert branch and all("moe" in c and ("moe_route" in c or "moe_experts" in c)
                          for c in branch)


# ---------------------------------------------------- zoo, files, counts

def test_published_config_has_23_8_billion_parameters():
    model = Lfm2Moe(Lfm2MoeConfig.from_dict(LFM2_24B_A2B))
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), jnp.zeros((1, 256), jnp.int32),
                           jnp.zeros((1, 256), jnp.int32)))
    n = sum(int(np.prod(v.shape)) for v in jax.tree_util.tree_leaves(shapes))
    expert_layer = 64 * 3 * 2048 * 1536 + 2048 * 64 + 64 + 2 * 2048
    conv, attention = 16_783_360, 10_485_888
    assert n == (2 * (conv + 3 * 2048 * 11776 + 2 * 2048)
                 + 28 * (conv + expert_layer) + 10 * (attention + expert_layer)
                 + 2048 + 65536 * 2048)
    assert abs(n - 23.84e9) < 0.01e9
    types = LFM2_24B_A2B["layer_types"]
    assert len(types) == 40 and types.count("full_attention") == 10
    assert [i for i, t in enumerate(types) if t == "full_attention"] == list(
        range(2, 40, 4))


def test_the_cells_share_holds_771_million_parameters():
    with open(os.path.join(ROOT, "benchmark", "configs", "LFM2-24B-A2B.json")) as f:
        cell = json.load(f)
    arch = dict(cell, num_experts=cell["published"]["num_experts"])
    model = Lfm2Moe(Lfm2MoeConfig.from_dict(arch, cell["expert_first"],
                                            cell["num_experts"]))
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), jnp.zeros((1, 256), jnp.int32),
                           jnp.zeros((1, 256), jnp.int32)))
    n = sum(int(np.prod(v.shape)) for v in jax.tree_util.tree_leaves(shapes))
    assert n == 771_275_136


def test_benchmark_file_differs_from_the_published_config_only_where_it_says():
    with open(os.path.join(ROOT, "benchmark", "configs", "LFM2-24B-A2B.json")) as f:
        cell = json.load(f)
    assert cell["reduced"] == ["num_hidden_layers", "num_dense_layers",
                               "layer_types", "num_experts", "vocab_size"]
    differs = [k for k, v in LFM2_24B_A2B.items() if cell[k] != v]
    assert sorted(differs) == sorted(cell["reduced"])
    assert cell["published"] == {k: LFM2_24B_A2B[k] for k in cell["reduced"]}
    assert cell["layer_types"] == LFM2_24B_A2B["layer_types"][1:6]
    assert (cell["num_hidden_layers"], cell["num_dense_layers"],
            cell["num_experts"], cell["expert_first"]) == (5, 1, 16, 0)
    assert cell["vocab_size"] * 8 == 65536
    for width in ("hidden_size", "intermediate_size", "moe_intermediate_size",
                  "num_attention_heads", "num_key_value_heads", "conv_L_cache",
                  "num_experts_per_tok"):
        assert cell[width] == LFM2_24B_A2B[width]


def test_zoo_holds_the_catalogs_config_key_for_key():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("the catalog of public architectures is not on this machine")
    with open(catalog) as f:
        rows = [json.loads(line) for line in f if line.strip()]
    (row,) = [r for r in rows if r["name"] == "LFM2-24B-A2B"]
    assert row["config"] == LFM2_24B_A2B


def test_flops_counted_from_shapes():
    from benchmark import flops_moe

    with open(os.path.join(ROOT, "benchmark", "configs", "LFM2-24B-A2B.json")) as f:
        cell = json.load(f)
    conv = 2048 * 6144 + 2048 * 2048
    attention = 2 * 2048 * 2048 + 2 * 2048 * 512
    routed = 2048 * 64 + 3 * 2048 * 1536      # 4 x 16 / 64 = one expert a token
    assert flops_moe.conv_macs(cell) == conv
    assert flops_moe.attention_macs(cell) == attention
    assert flops_moe.routed_macs(cell) == routed
    total = (4 * conv + attention + 3 * 2048 * 11776 + 4 * routed + 8192 * 2048)
    assert flops_moe.matmul_macs_per_token(cell) == total == cell[
        "matmul_macs_per_token"] == 204_996_608
    assert cell["train_flops_per_image"] == flops_moe.train_flops_per_sequence(
        cell) == 6 * 8192 * total


def test_buffer_fill_reads_held_rows_over_buffer_rows_in_the_traced_steps(tmp_path):
    """``moe_buffer_fill_pct``: the two counters' means over the steps logged
    inside the traced ones (after the checked and, in the GLM cell, the
    settling steps); nothing for a program that logs no buffer rows."""
    from benchmark.byname import load_module

    reader = load_module(os.path.join(ROOT, "benchmark", "metrics",
                                      "moe_buffer_fill_pct.py"), "fill_reader")
    logged = {"train_moe_assignments": {3: 900.0, 13: 40000.0, 23: 36000.0, 33: 1.0},
              "train_moe_buffer_rows": {3: 131072.0, 13: 61440.0, 23: 49152.0, 33: 1.0}}

    def run(names, **traffic):
        with open(tmp_path / "metrics.jsonl", "w") as f:
            for name in names:
                for step, value in logged[name].items():
                    f.write(json.dumps({"name": name, "step": step, "value": value}) + "\n")
        return {"window": {"workdir": str(tmp_path)},
                "traffic": {"trace_steps": [10, 25], "check_steps": 3, **traffic}}

    assert reader.read(run(logged)) == pytest.approx(100 * 76000 / 110592)
    assert reader.read(run(logged, settle_steps=10)) == pytest.approx(
        100 * 36001 / 49153)
    assert reader.read(run(["train_moe_assignments"])) is None   # the parent


# ------------------------------------------------------ optimizer, trainer

def test_decay_mask_leaves_out_every_norm_and_the_bias_is_no_parameter():
    from deep_vision_tpu.core.optim import _weight_decay_mask

    batch = small_rows()
    variables = jax.eval_shape(
        lambda: small_model(4, 4).init(jax.random.PRNGKey(0), batch["tokens"],
                                       batch["segment_ids"]))
    assert sorted(flat(variables["batch_stats"])) == [
        "layer_1/feed_forward/expert_bias", "layer_2/feed_forward/expert_bias"]
    params = variables["params"]
    assert not any(k.endswith("expert_bias") for k in flat(params))
    mask = flat(_weight_decay_mask(params))
    decayed = {k.rsplit("/", 1)[-1] for k, v in mask.items() if v}
    spared = {"/".join(k.rsplit("/", 2)[-2:]) for k, v in mask.items() if not v}
    assert decayed == {"kernel", "embedding", "conv_kernel", "router",
                       "experts_w1", "experts_w3", "experts_w2"}
    assert spared == {"q_layernorm/scale",
                      "k_layernorm/scale", "operator_norm/scale",
                      "ffn_norm/scale", "final_norm/scale"}


def trainer_at_the_test_size(tmp_path, mesh1, first=4, count=4):
    from deep_vision_tpu.core.config import get_config
    from deep_vision_tpu.core.trainer import Trainer
    from deep_vision_tpu.tasks.language_modeling import LanguageModelingTask

    cfg = get_config("lfm2_24b_a2b")
    cfg.extra["architecture"].update(SMALL)
    cfg.extra.update(sequence_length=LENGTH, expert_first=first, expert_count=count)
    cfg.half_precision, cfg.batch_size, cfg.log_every_steps = False, 2, 1
    return Trainer(cfg, cfg.model(), LanguageModelingTask(), mesh=mesh1,
                   workdir=str(tmp_path))


def test_three_steps_through_train_epoch_log_the_counters(tmp_path, mesh1):
    trainer = trainer_at_the_test_size(tmp_path, mesh1)
    batch = small_rows(rows=2)
    state = trainer.init_state(batch)
    leaves = flat(state.params)
    biases = flat(state.batch_stats)
    assert len(biases) == 2 and all(k.endswith("expert_bias") for k in biases)
    for i, name in enumerate(sorted(biases)):
        biases[name] = jax.device_put(jax.random.uniform(
            jax.random.PRNGKey(i), biases[name].shape, minval=-0.2, maxval=0.2),
            biases[name].sharding)
    state = state.replace(batch_stats=unflat(biases))
    before = jax.device_get({**biases, **{k: leaves[k] for k in (
        "layer_1/feed_forward/router", "layer_1/feed_forward/experts_w1")}})
    state = trainer.train_epoch(state, [batch] * 3, trainer.start_epoch)
    assert int(state.step) == 3 and int(state.bad_steps) == 0
    after = jax.device_get({**flat(state.params), **flat(state.batch_stats)})
    rate = trainer.config.extra["expert_bias_update_rate"]
    assert rate == 3e-2
    for name in biases:      # a step moves a bias by the rate, up or down,
        moved = (after[name] - before[name]) / rate     # unless its load is the mean
        np.testing.assert_allclose(np.abs(moved).round(), np.abs(moved), atol=1e-3)
        assert set(np.abs(moved).round()) <= {0.0, 1.0, 2.0, 3.0}
        assert np.mean(np.abs(moved).round() % 2 == 1) > 0.8
        assert (moved > 0).any() and (moved < 0).any()
    for name in ("layer_1/feed_forward/router", "layer_1/feed_forward/experts_w1"):
        assert np.abs(after[name] - before[name]).max() > 0
    # evaluation reads the biases and leaves them where they are
    trainer.eval_step(state, batch)
    for name, value in jax.device_get(flat(state.batch_stats)).items():
        np.testing.assert_array_equal(value, after[name])
    series = {}
    with open(tmp_path / "metrics.jsonl") as f:
        for line in f:
            row = json.loads(line)
            series.setdefault(row["name"], []).append(row["value"])
    assert len(series["train_loss"]) == 3 and np.isfinite(series["train_loss"]).all()
    assert series["train_moe_dropped"] == [0.0] * 3
    rungs = moe._capacities(2 * LENGTH * 4)      # two routed layers a step
    for rows, held in zip(series["train_moe_buffer_rows"],
                          series["train_moe_assignments"]):
        assert held <= rows and rows in {a + b for a in rungs for b in rungs}
    assert all(0 < v <= 2 * 2 * LENGTH * 4 for v in series["train_moe_assignments"])
    mean = 2 * LENGTH * 4 / 16
    assert all(mean <= v <= 2 * LENGTH for v in series["train_moe_max_load"])
    assert all(0 <= v < 2 * LENGTH for v in series["train_moe_unrouted_tokens"])
    assert "train_token_accuracy" in series


def test_cli_trains_three_steps_at_the_test_size(tmp_path, capsys):
    from deep_vision_tpu.cli import train

    overrides = [f"{k}={json.dumps(v)}" for k, v in SMALL.items()
                 if LFM2_24B_A2B[k] != v]
    overrides += [f"sequence_length={LENGTH}", "expert_first=4", "expert_count=4"]
    argv = ["-m", "lfm2_24b_a2b", "--synthetic", "--synthetic-size", "3",
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
    assert max(steps) == 3 and "train_moe_assignments" in names
    with pytest.raises(SystemExit):
        train.main(argv + ["--override", "no_such_key=1"])


def test_cli_builds_the_published_models_config():
    from deep_vision_tpu.core.config import get_config

    cfg = get_config("lfm2_24b_a2b")
    model = cfg.model()
    assert model.cfg.num_experts == model.cfg.held == 64
    assert len(model.cfg.layer_types) == 40 and model.cfg.vocab_size == 65536
    assert cfg.extra["sequence_length"] == 8192
    with pytest.raises(ValueError):
        Lfm2MoeConfig.from_dict(dict(LFM2_24B_A2B, conv_bias=True))
    with pytest.raises(ValueError):
        Lfm2MoeConfig.from_dict(LFM2_24B_A2B, 56, 16)
