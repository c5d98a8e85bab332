"""LFM2 mixture-of-experts decoder (``model_type: lfm2_moe``): gated
short-conv operators with a grouped-query attention operator where
``layer_types`` says so, each followed by a SwiGLU feed-forward block that
is dense in the first ``num_dense_layers`` layers and routed over
``num_experts`` experts in every later one, for training on packed
documents.  Every number comes from the published ``config.json`` (the zoo
holds it); the forms follow the published implementation:

    h = E[tokens]
    h = h + operator(RMSNorm(h));  h = h + ffn(RMSNorm(h))       # a layer
    logits = RMSNorm(h) E^T                                       # E tied

    conv:  [B, C, x] = W_in u;  y = W_out (C * conv3(B * x))
    attention:  q, k per-head RMSNorm, then rotary over the whole head
        (halves rotated, theta from ``rope_parameters``), scores q.k / sqrt(d)
    dense:  W2 (silu(W1 u) * W3 u)
    routed: s = sigmoid(W_r u);  sel = top-k(s + b);  w = s[sel] / (sum + 1e-6)
        ffn(u) = sum_{e in sel} w_e W2_e (silu(W1_e u) * W3_e u)
        b_e += rate * sign(mean load - load_e)      # after a training step

The conv is depthwise, causal, ``conv_L_cache`` taps, no bias, no
activation.  Conv, rotary positions and the attention mask all start anew
at a document's first token (``segment_ids``).  The router's product, the
sigmoid and the top-k are float32 (``ops/moe.py``).  The published
``routed_scaling_factor`` is 1 and ``REQUIRED`` refuses another: the model
calls ``ops/moe.py::route`` with its defaults (``scale`` 1, ``eps`` 1e-6),
which give the weights above to the bit.

**The selection bias** ``b`` (``use_expert_bias``) chooses and does not weigh.
It is no parameter: no gradient reaches it and the optimizer never sees it.
It is state that a training step's forward pass updates from the loads it
saw, over the router's whole width (balancing without an auxiliary loss,
``ops/moe.py::balanced_bias``), and lives where the trainer carries such
state, in the ``batch_stats`` collection beside a BatchNorm's running
statistics: updated when that collection is mutable, read when it is not.
The published config and code give no rule and no rate;
``expert_bias_update_rate`` is assumed (the zoo says from where).

**The share.**  ``num_experts`` is the router's width, always the published
one; ``expert_first`` and ``expert_count`` say which experts this chip
holds of an expert-parallel layer (default: all).  The layer routes over
all, computes the terms of its held experts and nothing else; that partial
sum goes on to the next layer.

Each layer is rematerialised in the backward pass.  Kept between the
passes, beside a layer's ``(B, L, hidden)`` input, are the bfloat16 outputs
``KEPT`` names: the products against a weight matrix of the operators and of
the dense block, and the attention kernel's output with its float32
log-sum-exp a row and head (``ops/attention.py`` names both in its forward
rule), so that none of them runs twice and the backward kernel builds a
block's probabilities from the log-sum-exp.  The routed block keeps its routing (``ops/moe.py``'s
``moe_routing``: the chosen experts and the sort, integers of 0.5 MB a
layer), so the top-k and the sort run once; its rows are not kept: the
block's backward pass gathers them and runs the grouped products again, in
buffers of the capacity the layer chose on the device for the rows it holds
(``ops/moe.py``'s ladder: 3/8 of the worst case's ``L x k`` rows while 16
of 64 experts hold their even share or half as much again, PERF.md s6, PR 38).

Scopes ``embed``, ``conv_op``, ``gqa_op``, ``dense_ffn``, ``moe`` (inside it
``moe_route`` and ``moe_experts``, ``ops/moe.py``) and ``lm_head`` name the
parts in a device trace; a layer's norm and residual go by its operator's
or its block's scope.  The model returns the logits and six routing
counters (``moe_assignments`` summed over the expert layers,
``moe_max_load`` the largest, ``moe_unrouted_tokens`` their mean,
``moe_dropped`` summed, ``moe_buffer_rows`` the rows of the buffers the
layers chose, summed, ``moe_bias_lift`` the mean over layers and tokens of
``sum_k w_k b_{e_k}``: the selection bias of a token's experts averaged
with the weights it gives them), which ``LanguageModelingTask`` hands on
as the step's metrics.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from deep_vision_tpu.models.granite_hybrid import RMSNorm, causal_conv
from deep_vision_tpu.ops import attention, moe


@dataclasses.dataclass(frozen=True)
class Lfm2MoeConfig:
    vocab_size: int
    hidden_size: int
    intermediate_size: int
    moe_intermediate_size: int
    layer_types: tuple
    num_dense_layers: int
    num_experts: int            # the router's width
    num_experts_per_tok: int
    num_attention_heads: int
    num_key_value_heads: int
    conv_L_cache: int
    norm_eps: float
    rope_theta: float
    expert_first: int = 0       # the experts held here:
    expert_count: int | None = None     # [first, first + count); None: all
    expert_bias_update_rate: float = 0.0    # a training step's move of a bias

    # what the equations above assume of a config.json; anything else is
    # another model
    REQUIRED = {"conv_bias": False, "norm_topk_prob": True,
                "use_expert_bias": True, "routed_scaling_factor": 1,
                "model_type": "lfm2_moe"}

    @classmethod
    def from_dict(cls, config: dict, expert_first: int = 0,
                  expert_count: int | None = None,
                  expert_bias_update_rate: float = 0.0) -> "Lfm2MoeConfig":
        for key, value in cls.REQUIRED.items():
            if config.get(key) != value:
                raise ValueError(f"{key}: this decoder is written for "
                                 f"{value!r}, the config says {config.get(key)!r}")
        rope = config["rope_parameters"]
        if rope.get("rope_type") != "default":
            raise ValueError(f"rope_type {rope.get('rope_type')!r}")
        fields = {f.name for f in dataclasses.fields(cls)} - {
            "expert_first", "expert_count",    # the share is not the model's
            "expert_bias_update_rate"}         # nor is the trainer's rate
        kw = {k: v for k, v in config.items() if k in fields}
        depth = int(config["num_hidden_layers"])
        if depth > len(config["layer_types"]):
            raise ValueError(f"{depth} layers of {len(config['layer_types'])} "
                             f"layer_types")
        kw["layer_types"] = tuple(config["layer_types"][:depth])
        out = cls(**kw, rope_theta=float(rope["rope_theta"]),
                  expert_first=int(expert_first),
                  expert_count=None if expert_count is None else int(expert_count),
                  expert_bias_update_rate=float(expert_bias_update_rate))
        if not 0 <= out.expert_first <= out.expert_first + out.held <= out.num_experts:
            raise ValueError(f"experts [{out.expert_first}, "
                             f"{out.expert_first + out.held}) of {out.num_experts}")
        return out

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def held(self) -> int:
        return (self.num_experts - self.expert_first
                if self.expert_count is None else self.expert_count)


# what a rematerialised layer keeps between the passes beside its input
KEPT = ("conv_in_proj", "q_proj", "k_proj", "v_proj", attention.OUT,
        attention.LSE, "operator_out_proj", "ffn_w1", "ffn_w3", moe.ROUTING)
COUNTERS = ("assignments", "max_load", "unrouted_tokens", "dropped",
            "buffer_rows", "bias_lift")


def model_counters(per_layer: list) -> dict:
    """The routed layers' ``COUNTERS`` as the model hands them on."""
    stacked = {k: jnp.stack([c[k] for c in per_layer]) if per_layer
               else jnp.zeros((1,), jnp.float32) for k in COUNTERS}
    return {"moe_assignments": stacked["assignments"].sum(),
            "moe_max_load": stacked["max_load"].max(),
            "moe_unrouted_tokens": stacked["unrouted_tokens"].mean(),
            "moe_dropped": stacked["dropped"].sum(),
            "moe_buffer_rows": stacked["buffer_rows"].sum(),
            "moe_bias_lift": stacked["bias_lift"].mean()}


def _normal():
    return nn.initializers.normal(0.02)


def _dense(features, dtype, name):
    return nn.Dense(features, use_bias=False, dtype=dtype,
                    kernel_init=_normal(), name=name)


def document_positions(segment_ids):
    """Each token's distance from its document's first token in the row."""
    at = jnp.arange(segment_ids.shape[1], dtype=jnp.int32)[None, :]
    first = jnp.pad(segment_ids[:, 1:] != segment_ids[:, :-1],
                    ((0, 0), (1, 0)), constant_values=True)
    return at - jax.lax.cummax(jnp.where(first, at, 0), axis=1)


def rotary(x, positions, theta: float):
    """``x`` (B, L, H, D) float32, ``positions`` (B, L): the head's halves
    rotated, pair ``i`` by ``position x theta^(-2i / D)``."""
    half = x.shape[-1] // 2
    rate = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = positions.astype(jnp.float32)[..., None, None] * rate
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


class ShortConv(nn.Module):
    cfg: Lfm2MoeConfig
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, u, segment_ids, positions):
        cfg, f32 = self.cfg, jnp.float32
        bcx = checkpoint_name(
            _dense(3 * cfg.hidden_size, self.dtype, "in_proj")(u), "conv_in_proj")
        b, c, x = jnp.split(bcx.astype(f32), 3, axis=-1)
        # torch's Conv1d default, which the published implementation leaves
        bound = cfg.conv_L_cache ** -0.5
        taps = self.param(
            "conv_kernel",
            lambda key, shape: jax.random.uniform(key, shape, f32, -bound, bound),
            (cfg.conv_L_cache, cfg.hidden_size))
        y = c * causal_conv(b * x, taps, 0.0, segment_ids)
        return checkpoint_name(
            _dense(cfg.hidden_size, self.dtype, "out_proj")(y.astype(self.dtype)),
            "operator_out_proj")


class GroupedQueryAttention(nn.Module):
    cfg: Lfm2MoeConfig
    attention_block: int = 1024
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, u, segment_ids, positions):
        cfg = self.cfg

        def heads(count, name, norm):
            y = checkpoint_name(
                _dense(count * cfg.head_dim, self.dtype, name)(u), name)
            y = y.reshape(*y.shape[:2], count, cfg.head_dim)
            if norm is None:
                return y
            y = RMSNorm(cfg.norm_eps, jnp.float32, name=norm)(y)
            return rotary(y, positions, cfg.rope_theta).astype(self.dtype)

        out = attention.causal_attention(
            heads(cfg.num_attention_heads, "q_proj", "q_layernorm"),
            heads(cfg.num_key_value_heads, "k_proj", "k_layernorm"),
            heads(cfg.num_key_value_heads, "v_proj", None),
            segment_ids, cfg.head_dim ** -0.5, self.attention_block)
        return checkpoint_name(
            _dense(cfg.hidden_size, self.dtype, "out_proj")(
                out.reshape(*out.shape[:2], -1)), "operator_out_proj")


class DenseFeedForward(nn.Module):
    cfg: Lfm2MoeConfig
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, u):
        width = self.cfg.intermediate_size
        gate = checkpoint_name(_dense(width, self.dtype, "w1")(u), "ffn_w1")
        value = checkpoint_name(_dense(width, self.dtype, "w3")(u), "ffn_w3")
        return _dense(self.cfg.hidden_size, self.dtype, "w2")(
            nn.silu(gate) * value)


class RoutedFeedForward(nn.Module):
    """This chip's experts of the layer; the router and the selection bias
    at the layer's whole width."""

    cfg: Lfm2MoeConfig
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, u):
        cfg = self.cfg
        hidden, width, held = cfg.hidden_size, cfg.moe_intermediate_size, cfg.held
        router = self.param("router", _normal(), (hidden, cfg.num_experts))
        state = self.variable("batch_stats", "expert_bias", jnp.zeros,
                              (cfg.num_experts,), jnp.float32)
        bias = state.value
        w1 = self.param("experts_w1", _normal(), (held, hidden, width))
        w3 = self.param("experts_w3", _normal(), (held, hidden, width))
        w2 = self.param("experts_w2", _normal(), (held, width, hidden))
        rows = u.reshape(-1, hidden)
        indices, weights = moe.route(rows, router, bias, cfg.num_experts_per_tok)
        out, counters = moe.routed_experts(rows, indices, weights, w1, w3, w2,
                                           cfg.expert_first)
        # the chosen experts' biases averaged with the weights a token gives
        # them, all k of them: it rises if the bias ever comes to weigh
        counters["bias_lift"] = jnp.mean(jnp.sum(weights * bias[indices], -1))
        if self.is_mutable_collection("batch_stats") and not self.is_initializing():
            state.value = moe.balanced_bias(bias, indices,
                                            cfg.expert_bias_update_rate)
        return out.reshape(u.shape), counters


class Lfm2MoeLayer(nn.Module):
    cfg: Lfm2MoeConfig
    kind: str
    routed: bool
    attention_block: int = 1024
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, h, segment_ids, positions):
        cfg = self.cfg
        if self.kind == "conv":
            scope, operator = "conv_op", ShortConv(cfg, self.dtype, name="operator")
        elif self.kind == "full_attention":
            scope, operator = "gqa_op", GroupedQueryAttention(
                cfg, self.attention_block, self.dtype, name="operator")
        else:
            raise ValueError(f"unknown layer type {self.kind!r}")
        with jax.named_scope(scope):
            u = RMSNorm(cfg.norm_eps, self.dtype, name="operator_norm")(h)
            h = h + operator(u, segment_ids, positions).astype(h.dtype)
        with jax.named_scope("moe" if self.routed else "dense_ffn"):
            u = RMSNorm(cfg.norm_eps, self.dtype, name="ffn_norm")(h)
            if self.routed:
                out, counters = RoutedFeedForward(
                    cfg, self.dtype, name="feed_forward")(u)
            else:
                out = DenseFeedForward(cfg, self.dtype, name="feed_forward")(u)
                counters = dict.fromkeys(COUNTERS, jnp.zeros((), jnp.float32))
            h = h + out.astype(h.dtype)
        return h, counters


RematLayer = nn.remat(
    Lfm2MoeLayer, policy=jax.checkpoint_policies.save_only_these_names(*KEPT))


class Lfm2Moe(nn.Module):
    """``tokens``, ``segment_ids`` (B, L) int32 -> logits (B, L, vocab)
    float32 and the routing counters.  ``train`` is accepted for the
    trainer's sake: nothing in the model depends on it."""

    cfg: Lfm2MoeConfig
    attention_block: int = 1024
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, tokens, segment_ids, train: bool = False):
        cfg = self.cfg
        table = self.param("embedding", _normal(),
                           (cfg.vocab_size, cfg.hidden_size))
        with jax.named_scope("embed"):
            h = table.astype(self.dtype)[tokens]
            positions = document_positions(segment_ids)
        per_layer = []
        for i, kind in enumerate(cfg.layer_types):
            routed = i >= cfg.num_dense_layers
            h, counters = RematLayer(cfg, kind, routed, self.attention_block,
                                     self.dtype, name=f"layer_{i}")(
                                         h, segment_ids, positions)
            if routed:
                per_layer.append(counters)
        with jax.named_scope("lm_head"):
            h = RMSNorm(cfg.norm_eps, self.dtype, name="final_norm")(h)
            logits = jnp.einsum("bld,vd->blv", h, table.astype(self.dtype),
                                preferred_element_type=jnp.float32)
        return logits, model_counters(per_layer)
