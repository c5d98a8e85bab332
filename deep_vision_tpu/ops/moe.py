"""Routed experts for one chip's share of an expert-parallel layer.

``route`` scores every token against the router's whole width (all the
layer's experts, wherever they live) and chooses ``k`` of them;
``balanced_bias`` moves the selection bias against the loads that choice
gave (the state a training step carries to the next; no gradient);
``routed_experts`` computes, for the ``count`` experts
``[first, first + count)`` this chip holds, the chosen experts' SwiGLU of
the tokens sent to them, weighs and sums it per token.  A token none of
whose experts is held gets zero; what the absent experts would have added
is nobody's here (no mesh axis, no exchange, nothing that stands in for
them).

Dropless.  The ``T x k`` assignments are sorted by expert with the ones
that fall on absent experts last, the tokens' rows gathered in that order
and handed to a grouped product (megablox ``gmm``: a Pallas kernel whose
grid runs over the row tiles the groups cover) whose group sizes are the
held experts' loads.  No load is ever cut whatever the imbalance: the
kernel leaves rows past the last group unwritten; they are zeroed where the
weights are applied, and their cotangent is zeroed on the way back, so what
lies there reaches neither the result nor a gradient.

The row buffers take a capacity chosen on the device, a layer and a step.
The products follow the groups, but the gathers, the SwiGLU between the
products, the weights and the masks run over every row of a buffer, and a
chip that holds a quarter or an eighth of the experts holds about that share
of the assignments.  So the part of the block that has rows (``_rung``)
exists once for each capacity of a short ladder (``LADDER``: fractions of
``T x k``, the last the worst case, every assignment held) and
``lax.switch`` runs the first that holds the ``rows`` counted this time.  The
sort puts held rows first, so a buffer of ``C >= rows`` rows is the sort's
first ``C`` places; a token's assignment past them is an absent expert's and
reads zero.  The choice, the sort, the loads and the counts stay outside at
``T x k`` (integers and ``(T, k)`` floats).  The top rung is the block with
every buffer at ``T x k``; the counter ``buffer_rows`` says which ran.

The switch is differentiated inside its branches (``_switched``, a
``custom_vjp`` whose backward pass is a second switch, each branch the
``jax.vjp`` of its own forward).  Autodiff of ``lax.switch`` joins the
branches' residuals, so every branch would hand back zeros of every other
capacity's buffers, which gives the gain back; here what lies between the
passes is the block's inputs, the same whatever ran.  Under a
rematerialised layer that is the work done before (forward; recomputation
and backward), the recomputation now inside the backward branch.

Rows move by gathers alone, forward and backward.  ``_dispatch`` (token ->
sorted rows) and ``_combine`` (sorted rows -> token, summed over its ``k``)
are each other's transposes and say so with a ``custom_vjp`` each; autodiff
would write a scatter-add over the buffer's rows instead.  The weights are
applied to the sorted rows, so no ``(T, k, D)`` tensor is ever laid out.

The router's product, the sigmoid and the top-k are float32 at
``Precision.HIGHEST`` whatever the model's compute dtype: top-k is
discontinuous and a rounded score flips a token's fourth and fifth expert.
What the choice and the sort produce (integers, a few hundred KB) is named
``moe_routing`` for ``jax.checkpoint`` policies: a rematerialised layer that
keeps it sorts once.  The scopes ``moe_route`` (router, top-k, sort,
gathers, weights) and ``moe_experts`` (the weights' cast, the grouped
products and the SwiGLU between them) name the two parts in a device trace,
inside the branches too, where a ``buffer_<rows>`` scope names the capacity.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

HIGHEST = jax.lax.Precision.HIGHEST
NORM_EPS = 1e-6  # the LFM2 code's, added to the chosen scores' sum (route's default)
ROUTING = "moe_routing"  # checkpoint name of the choice and of the sort


def route(u, router, bias, k: int, scale: float = 1.0, eps: float = NORM_EPS):
    """``u`` (T, D), ``router`` (D, E), ``bias`` (E,).  Returns the chosen
    experts (T, k) int32 and their weights (T, k) float32: sigmoid scores,
    chosen by ``score + bias`` (the bias chooses and does not weigh),
    normalised over all ``k`` chosen (their sum + ``eps``) and multiplied by
    ``scale`` (a model's ``routed_scaling_factor``).  The gradient reaches
    ``router`` through the weights alone; none flows through the choice or
    ``bias``."""
    f32 = jnp.float32
    with jax.named_scope("moe_route"):
        scores = jax.nn.sigmoid(jnp.dot(u.astype(f32), router.astype(f32),
                                        precision=HIGHEST))
        _, indices = jax.lax.top_k(jax.lax.stop_gradient(scores + bias), k)
        indices = checkpoint_name(indices, ROUTING)
        chosen = jnp.take_along_axis(scores, indices, axis=-1)
        weights = chosen / (chosen.sum(-1, keepdims=True) + eps)
        return indices, weights if scale == 1.0 else weights * scale


def balanced_bias(bias, indices, rate: float):
    """The selection bias after one step of balancing without an auxiliary
    loss (Wang et al., arXiv:2408.15664): every expert of the router's whole
    width whose load in this batch (``indices`` (T, k), held here or not)
    lies over the mean ``T k / E`` loses ``rate``, every one under it gains
    ``rate``.  No gradient is involved; the caller keeps the result as state
    for the next step."""
    with jax.named_scope("moe_route"):
        experts = jnp.arange(bias.shape[0], dtype=indices.dtype)
        loads = jnp.sum(indices.reshape(-1, 1) == experts, axis=0,
                        dtype=jnp.float32)
        return bias + rate * jnp.sign(indices.size / bias.shape[0] - loads)


def _rows_at(rows, index):
    """``rows[index]``, zero where ``index`` lies past the buffer's end: in a
    buffer cut to a rung's capacity an absent expert's assignment has no
    row.  A gather in both cases."""
    return rows.at[index].get(mode="fill", fill_value=0)  # dvtlint: disable=DVT007 — an array's, not a queue's


def _gathered_sum(rows, slots):
    """``sum_j rows[slots[:, j]]`` in float32."""
    return sum(_rows_at(rows, slots[:, j]).astype(jnp.float32)
               for j in range(slots.shape[1]))


@jax.custom_vjp
def _dispatch(u, token, slots, live):
    """Sorted row ``r`` is token ``token[r]``'s; ``slots`` (T, k) are the
    rows of each token's assignments, ``live`` (C, 1) the rows computed."""
    return u[token]


def _dispatch_fwd(u, token, slots, live):
    return u[token], (slots, live)


def _dispatch_bwd(saved, g):
    slots, live = saved
    g = jnp.where(live, g, 0)      # the kernels leave the other rows unwritten
    return _gathered_sum(g, slots).astype(g.dtype), None, None, None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def _combine(y, token, slots):
    """Token ``t`` gets the sum of its ``k`` rows ``y[slots[t]]``."""
    return _gathered_sum(y, slots).astype(y.dtype)


def _combine_fwd(y, token, slots):
    return _combine(y, token, slots), token


def _combine_bwd(token, g):
    return g[token], None, None


_combine.defvjp(_combine_fwd, _combine_bwd)


@jax.custom_vjp
def _permute(v, order, inverse):
    """``v[order]`` for the first ``order.size`` places of the sort."""
    return v[order]


_permute.defvjp(lambda v, order, inverse: (v[order], inverse),
                lambda inverse, g: (_rows_at(g, inverse), None, None))


# rows of a tile; the most of K and of N a grid step.  On the chip at the
# benchmark's shapes (PERF.md s6, PR 33: one layer forward + backward) 256 x
# 1,024 read 15.6 ms, 512 x 1,024 16.0, 256 x 512 16.6, 512 x 512 16.6, and
# XLA's own jax.lax.ragged_dot 17.5; 512 x 2,048 does not fit VMEM.
TILE = (256, 1024)


def _tile(size: int, most: int) -> int:
    """The largest multiple of 128 that divides ``size`` and is at most
    ``most``, so that no grid step works on part of a tile; the whole of a
    size that has none (the tests')."""
    fits = [t for t in range(128, min(size, most) + 1, 128) if size % t == 0]
    return max(fits, default=size)


def _grouped_product(x, w, loads):
    """Rows ``[sum(loads[:e]), sum(loads[:e + 1]))`` of ``x`` (M, K) times
    ``w[e]`` (K, N), in ``x``'s dtype; rows past the last group are left
    unwritten.  ``M`` is ``T x k``, a multiple of 32.  Interpreted off the
    chip."""
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    tiling = (math.gcd(x.shape[0], TILE[0]), _tile(x.shape[1], TILE[1]),
              _tile(w.shape[2], TILE[1]))
    return gmm(x, w, loads, preferred_element_type=x.dtype, tiling=tiling,
               interpret=jax.default_backend() != "tpu")


# The row buffers' capacities as fractions of the worst case T x k: one and a
# half times the even share of a chip that holds a quarter of the router's
# width, and the worst case itself.  Read on the chip (PERF.md s6, PR 38): a
# block of 17-22 ms is 5-6 ms shorter in 3/8 of the rows than in all; a rung
# at 3/16 took 1 ms more off and one at 3/4 1 ms off steps that are rare, and
# every rung adds a sixth to the step's compile and 5 s to a warm start.
LADDER = ((3, 8), (1, 1))


def _capacities(assignments: int) -> tuple[int, ...]:
    """The ladder's rungs for ``assignments`` = T x k, each rounded up to
    whole row tiles and none past the worst case; rungs that coincide (the
    tests' sizes) are one."""
    rows = TILE[0]
    return tuple(sorted({min(assignments, -(-assignments * n // (d * rows)) * rows)
                         for n, d in LADDER}))


def _rung(capacity: int, routing, u, weights, w1, w3, w2):
    """The block in buffers of ``capacity`` rows, which hold every held
    assignment: ``routing`` is the sort (``order``, ``inverse``, the held
    experts' ``loads`` and their sum ``rows`` <= ``capacity``), ``weights``
    (T, k) are zero where an expert is absent.  Returns the block's result
    (T, D) and the number of rows the products computed."""
    order, inverse, loads, rows = routing
    (tokens, k), dtype, f32 = weights.shape, u.dtype, jnp.float32
    # transforms of a branch (jvp, transpose) wrap this component of an
    # operation's name and leave ``moe_route`` / ``moe_experts`` whole
    with jax.named_scope(f"buffer_{capacity}"):
        with jax.named_scope("moe_route"):
            order = order[:capacity]                # held rows sort first
            token, slots = order // k, inverse.reshape(tokens, k)
            live = (jnp.arange(capacity) < rows)[:, None]
            weight = _permute(weights.reshape(-1), order, inverse)[:, None]
            x = _dispatch(u, token, slots, live)
        with jax.named_scope("moe_experts"):
            gate = _grouped_product(x, w1.astype(dtype), loads)
            value = _grouped_product(x, w3.astype(dtype), loads)
            hidden = (jax.nn.silu(gate.astype(f32)) * value.astype(f32)).astype(dtype)
            y = _grouped_product(hidden, w2.astype(dtype), loads)
        with jax.named_scope("moe_route"):
            y = (jnp.where(live, y, 0).astype(f32) * weight).astype(dtype)
            computed = jnp.sum(jnp.any(y != 0, axis=-1), dtype=jnp.int32)
            return _combine(y, token, slots), computed


def _rung_backward(capacity: int, routing, operands, g):
    """The cotangents of ``_rung``'s five ``operands`` for ``g`` on its
    result, from its own forward run again."""
    _, pull, _ = jax.vjp(functools.partial(_rung, capacity, routing), *operands,
                         has_aux=True)
    return pull(g)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _switched(capacities, rung, routing, *operands):
    """``_rung(capacities[rung], routing, *operands)``, one branch a capacity."""
    return jax.lax.switch(rung, [functools.partial(_rung, c) for c in capacities],
                          routing, *operands)


def _switched_fwd(capacities, rung, routing, *operands):
    return _switched(capacities, rung, routing, *operands), (rung, routing, operands)


def _switched_bwd(capacities, saved, cotangents):
    """The branch that ran differentiates its own forward: ``saved`` is the
    block's inputs, one shape whatever ran (the module's docstring says why)."""
    rung, routing, operands = saved
    return (None, None) + jax.lax.switch(
        rung, [functools.partial(_rung_backward, c) for c in capacities],
        routing, operands, cotangents[0])


_switched.defvjp(_switched_fwd, _switched_bwd)


def routed_experts(u, indices, weights, w1, w3, w2, first: int = 0):
    """``u`` (T, D); ``indices``, ``weights`` (T, k) from ``route``; ``w1``,
    ``w3`` (count, D, F) and ``w2`` (count, F, D), the experts
    ``[first, first + count)``.  Returns the held experts' part of the
    routed block (T, D) in ``u``'s dtype and the layer's counters (float32
    scalars): ``assignments`` that fell on held experts, ``max_load`` (the
    fullest held expert's rows), ``unrouted_tokens`` (tokens none of whose
    experts is held), ``dropped`` (held assignments whose row came out of
    the grouped products all zero, as a row left out does where the weights
    are applied: read off the products' result, not off the loads they were
    handed, and 0 while nothing is left out) and ``buffer_rows`` (the
    capacity the row buffers took this time: the first of the ladder's that
    holds the assignments)."""
    tokens, k = indices.shape
    count, f32 = w1.shape[0], jnp.float32
    capacities = _capacities(tokens * k)
    with jax.named_scope("moe_route"):
        local = indices.reshape(-1) - first
        held = (local >= 0) & (local < count)
        key = jnp.where(held, local, count)         # absent experts sort last
        order = jnp.argsort(key, stable=True).astype(jnp.int32)
        inverse = jnp.zeros_like(order).at[order].set(
            jnp.arange(order.size, dtype=jnp.int32))
        loads = jnp.sum(key[:, None] == jnp.arange(count)[None, :], axis=0,
                        dtype=jnp.int32)
        order, inverse, loads = checkpoint_name((order, inverse, loads), ROUTING)
        rows = jnp.sum(held, dtype=jnp.int32)
        rung = jnp.sum(rows > jnp.asarray(capacities[:-1], jnp.int32),
                       dtype=jnp.int32)             # the first that holds them
        weights = jnp.where(held.reshape(tokens, k), weights, 0.0)
    out, computed = _switched(capacities, rung, (order, inverse, loads, rows),
                              u, weights, w1, w3, w2)
    with jax.named_scope("moe_route"):
        counters = {
            "assignments": rows.astype(f32),
            "max_load": loads.max().astype(f32),
            "unrouted_tokens": jnp.sum(~held.reshape(tokens, k).any(-1),
                                       dtype=f32),
            "dropped": (rows - computed).astype(f32),
            "buffer_rows": jnp.asarray(capacities, f32)[rung],
        }
    return out, counters
