"""GLM mixture-of-experts decoder with latent attention (``model_type:
glm4_moe_lite``): every layer a multi-head latent attention operator (MLA,
DeepSeek-V2 arXiv:2405.04434) followed by a SwiGLU feed-forward block that
is dense in the first ``first_k_dense_replace`` layers and, in every later
one, routed over ``n_routed_experts`` experts beside one shared expert that
every token passes; an untied output head; and a multi-token-prediction
module (DeepSeek-V3 arXiv:2412.19437 s2.2) that predicts the token after
the next, for training on packed documents.  Every number comes from the
published ``config.json`` (the zoo holds it):

    h = E[tokens]
    h = h + mla(RMSNorm(h));  h = h + ffn(RMSNorm(h))             # a layer
    logits = RMSNorm(h_L) W_out^T                                  # untied

    mla(u):  c_q = RMSNorm(W_qa u)                     # q_lora_rank
             [q_nope | q_rope] = W_qb c_q   per head   # H x (nope | rope)
             [c_kv | k_rope] = W_kva u                 # kv_lora_rank | rope
             c_kv = RMSNorm(c_kv);  [k_nope | v] = W_kvb c_kv   per head
             q = [q_nope | rot(q_rope)];  k = [k_nope | rot(k_rope)]
             out = W_o softmax(q.k / sqrt(nope + rope)) v
    dense:   W2 (silu(W1 u) * W3 u)
    routed:  s = sigmoid(W_r u);  sel = top-k(s + b)
             w = routed_scaling_factor * s[sel] / (sum + 1e-20)
             ffn(u) = sum_{e in sel} w_e W2_e (silu(W1_e u) * W3_e u)
                      + W2_s (silu(W1_s u) * W3_s u)               # shared
             b_e += rate * sign(mean load - load_e)   # after a training step
    module:  x_t = W_eh [RMSNorm_e(E[tokens[t+1]]) | RMSNorm_h(h_L[t])]
             y = one routed layer (x);  logits2[t] = RMSNorm_m(y[t]) W_out^T

``k_rope`` is ONE rotary key a token, shared by all heads: it is broadcast
over the heads as ``k`` is laid out for the attention op, which takes heads
of ``nope + rope`` for queries and keys and of ``v_head_dim`` for values
(the same width here, which ``from_dict`` insists on).  ``rot`` rotates the
halves of the ``rope`` dimensions (``models/lfm2_moe.py::rotary``); rotary
positions, the attention mask and the module's next token all start anew
at a document's first token.  ``h_L`` is the last layer's stream before
the final norm; at a row's last position, and wherever ``t + 1`` lies in
another document, the module's input is no prediction's and the task gives
those positions no weight (``tasks/language_modeling.py``).  The table and
the head are shared between the model and its module.

The selection bias, the share of the experts (``expert_first``,
``expert_count`` of the router's ``n_routed_experts``) and what a share
computes are ``models/lfm2_moe.py``'s, to the letter: the bias is state in
``batch_stats`` that a training step's forward pass moves and no gradient
does; a chip routes over all experts and computes its own.  The shared
expert is on every chip of an expert-parallel layer, each computes it
whole, and it is counted once where the shares are added up.

Each layer, the module's among them, is rematerialised in the backward
pass and keeps what ``KEPT`` names beside its input.

Scopes: ``embed``, ``mla_op`` (inside it ``mla_core`` around the attention
op and nothing else), ``dense_block``, ``routed_ffn`` (inside it
``shared_expert`` and ``ops/moe.py``'s ``moe_route`` / ``moe_experts``),
``lm_head`` and ``mtp`` (around the whole module, with its own ``mla_op``,
``routed_ffn`` and ``lm_head`` inside).  None of them, and no module name,
is another model's (``mamba``, ``attention``, ``mlp``, ``moe``, ``conv_op``,
``gqa_op``, ``dense_ffn``): the benchmark's readers tell models apart by
them.  The model returns ``(logits, logits2, counters)``: ``logits2`` is
None without a module; the counters are the six of ``models/lfm2_moe.py``
over every routed layer, the module's among them.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from deep_vision_tpu.models.granite_hybrid import RMSNorm
from deep_vision_tpu.models.lfm2_moe import (
    COUNTERS,
    document_positions,
    model_counters,
    rotary,
)
from deep_vision_tpu.ops import attention, moe

ROUTE_EPS = 1e-20  # the published router's, added to the chosen scores' sum


@dataclasses.dataclass(frozen=True)
class Glm4MoeLiteConfig:
    vocab_size: int
    hidden_size: int
    intermediate_size: int
    moe_intermediate_size: int
    num_hidden_layers: int
    first_k_dense_replace: int
    n_routed_experts: int       # the router's width
    num_experts_per_tok: int
    routed_scaling_factor: float
    num_attention_heads: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    rms_norm_eps: float
    rope_theta: float
    num_nextn_predict_layers: int
    expert_first: int = 0       # the experts held here:
    expert_count: int | None = None     # [first, first + count); None: all
    expert_bias_update_rate: float = 0.0    # a training step's move of a bias

    # what the equations above assume of a config.json; anything else is
    # another model
    REQUIRED = {"n_group": 1, "topk_group": 1, "rope_scaling": None,
                "attention_bias": False, "norm_topk_prob": True,
                "partial_rotary_factor": 1, "topk_method": "noaux_tc",
                "hidden_act": "silu", "n_shared_experts": 1,
                "tie_word_embeddings": False, "model_type": "glm4_moe_lite"}

    @classmethod
    def from_dict(cls, config: dict, expert_first: int = 0,
                  expert_count: int | None = None,
                  expert_bias_update_rate: float = 0.0) -> "Glm4MoeLiteConfig":
        for key, value in cls.REQUIRED.items():
            if config.get(key) != value:
                raise ValueError(f"{key}: this decoder is written for "
                                 f"{value!r}, the config says {config.get(key)!r}")
        fields = {f.name for f in dataclasses.fields(cls)} - {
            "expert_first", "expert_count",    # the share is not the model's
            "expert_bias_update_rate"}         # nor is the trainer's rate
        out = cls(**{k: config[k] for k in fields},
                  expert_first=int(expert_first),
                  expert_count=None if expert_count is None else int(expert_count),
                  expert_bias_update_rate=float(expert_bias_update_rate))
        if config["num_key_value_heads"] != out.num_attention_heads:
            raise ValueError("latent attention gives every head its own key")
        if out.num_nextn_predict_layers not in (0, 1):
            raise ValueError(f"{out.num_nextn_predict_layers} prediction modules")
        if out.head_dim != out.v_head_dim:
            raise ValueError(f"keys of {out.head_dim}, values of {out.v_head_dim}: "
                             f"the attention op takes one width")
        if not 0 <= out.expert_first <= out.expert_first + out.held <= out.n_routed_experts:
            raise ValueError(f"experts [{out.expert_first}, "
                             f"{out.expert_first + out.held}) of {out.n_routed_experts}")
        return out

    @property
    def head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def held(self) -> int:
        return (self.n_routed_experts - self.expert_first
                if self.expert_count is None else self.expert_count)


# what a rematerialised layer keeps between the passes beside its input:
# the products against a weight matrix, the attention kernel's output and
# log-sum-exp, and the routing (PERF.md s6, PR 35, has the readings)
KEPT = ("q_a_proj", "q_b_proj", "kv_a_proj", "kv_b_proj", attention.OUT,
        attention.LSE, "o_proj", "ffn_w1", "ffn_w3", moe.ROUTING)


def _normal():
    return nn.initializers.normal(0.02)


def _dense(features, dtype, name, kept=None):
    def product(u):
        y = nn.Dense(features, use_bias=False, dtype=dtype,
                     kernel_init=_normal(), name=name)(u)
        return y if kept is None else checkpoint_name(y, kept)
    return product


class LatentAttention(nn.Module):
    cfg: Glm4MoeLiteConfig
    attention_block: int = 512
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, u, segment_ids, positions):
        cfg, dtype, f32 = self.cfg, self.dtype, jnp.float32
        heads, nope, rope = (cfg.num_attention_heads, cfg.qk_nope_head_dim,
                             cfg.qk_rope_head_dim)
        rows = u.shape[:2]

        def rot(x):
            return rotary(x.astype(f32), positions, cfg.rope_theta).astype(dtype)

        c_q = RMSNorm(cfg.rms_norm_eps, dtype, name="q_a_norm")(
            _dense(cfg.q_lora_rank, dtype, "q_a", "q_a_proj")(u))
        q = _dense(heads * cfg.head_dim, dtype, "q_b", "q_b_proj")(c_q)
        q = q.reshape(*rows, heads, cfg.head_dim)
        kv = _dense(cfg.kv_lora_rank + rope, dtype, "kv_a", "kv_a_proj")(u)
        c_kv = RMSNorm(cfg.rms_norm_eps, dtype, name="kv_a_norm")(
            kv[..., :cfg.kv_lora_rank])
        up = _dense(heads * (nope + cfg.v_head_dim), dtype, "kv_b", "kv_b_proj")(c_kv)
        up = up.reshape(*rows, heads, nope + cfg.v_head_dim)
        # one rotary key a token, the same for every head
        k_rope = jnp.broadcast_to(rot(kv[..., None, cfg.kv_lora_rank:]),
                                  (*rows, heads, rope))
        q = jnp.concatenate([q[..., :nope], rot(q[..., nope:])], axis=-1)
        k, v = jnp.concatenate([up[..., :nope], k_rope], axis=-1), up[..., nope:]
        with jax.named_scope("mla_core"):
            out = attention.causal_attention(
                q, k, v, segment_ids, cfg.head_dim ** -0.5, self.attention_block)
        return _dense(cfg.hidden_size, dtype, "o", "o_proj")(
            out.reshape(*rows, -1))


class SwiGLU(nn.Module):
    """``W2 (silu(W1 u) * W3 u)``: the dense block and the shared expert."""

    width: int
    features: int
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, u):
        gate = _dense(self.width, self.dtype, "w1", "ffn_w1")(u)
        value = _dense(self.width, self.dtype, "w3", "ffn_w3")(u)
        return _dense(self.features, self.dtype, "w2")(nn.silu(gate) * value)


class RoutedFeedForward(nn.Module):
    """This chip's experts of the layer and the shared expert; the router
    and the selection bias at the layer's whole width."""

    cfg: Glm4MoeLiteConfig
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, u):
        cfg = self.cfg
        hidden, width, held = cfg.hidden_size, cfg.moe_intermediate_size, cfg.held
        router = self.param("router", _normal(), (hidden, cfg.n_routed_experts))
        state = self.variable("batch_stats", "expert_bias", jnp.zeros,
                              (cfg.n_routed_experts,), jnp.float32)
        bias = state.value
        w1 = self.param("experts_w1", _normal(), (held, hidden, width))
        w3 = self.param("experts_w3", _normal(), (held, hidden, width))
        w2 = self.param("experts_w2", _normal(), (held, width, hidden))
        rows = u.reshape(-1, hidden)
        indices, weights = moe.route(rows, router, bias, cfg.num_experts_per_tok,
                                     cfg.routed_scaling_factor, ROUTE_EPS)
        out, counters = moe.routed_experts(rows, indices, weights, w1, w3, w2,
                                           cfg.expert_first)
        # as models/lfm2_moe.py reads it, the weights' scale included
        counters["bias_lift"] = jnp.mean(jnp.sum(weights * bias[indices], -1))
        if self.is_mutable_collection("batch_stats") and not self.is_initializing():
            state.value = moe.balanced_bias(bias, indices,
                                            cfg.expert_bias_update_rate)
        with jax.named_scope("shared_expert"):
            shared = SwiGLU(width, hidden, self.dtype, name="shared")(u)
        return out.reshape(u.shape) + shared, counters


class Glm4MoeLiteLayer(nn.Module):
    cfg: Glm4MoeLiteConfig
    routed: bool
    attention_block: int = 512
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, h, segment_ids, positions):
        cfg = self.cfg
        with jax.named_scope("mla_op"):
            u = RMSNorm(cfg.rms_norm_eps, self.dtype, name="operator_norm")(h)
            h = h + LatentAttention(cfg, self.attention_block, self.dtype,
                                    name="operator")(
                                        u, segment_ids, positions).astype(h.dtype)
        with jax.named_scope("routed_ffn" if self.routed else "dense_block"):
            u = RMSNorm(cfg.rms_norm_eps, self.dtype, name="ffn_norm")(h)
            if self.routed:
                out, counters = RoutedFeedForward(
                    cfg, self.dtype, name="feed_forward")(u)
            else:
                out = SwiGLU(cfg.intermediate_size, cfg.hidden_size, self.dtype,
                             name="feed_forward")(u)
                counters = dict.fromkeys(COUNTERS, jnp.zeros((), jnp.float32))
            h = h + out.astype(h.dtype)
        return h, counters


RematLayer = nn.remat(
    Glm4MoeLiteLayer, policy=jax.checkpoint_policies.save_only_these_names(*KEPT))


def next_in_document(tokens, segment_ids):
    """``tokens[t + 1]`` where that is the same document's, else (a row's
    last position, a document's last) the token itself: a position whose
    prediction the task gives no weight."""
    after = jnp.concatenate([tokens[:, 1:], tokens[:, -1:]], axis=1)
    same = jnp.concatenate([segment_ids[:, 1:] == segment_ids[:, :-1],
                            jnp.zeros_like(segment_ids[:, :1], bool)], axis=1)
    return jnp.where(same, after, tokens)


class PredictionModule(nn.Module):
    """The stream of the last layer and the next token's embedding -> the
    stream the shared head reads for the token after the next."""

    cfg: Glm4MoeLiteConfig
    attention_block: int = 512
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, h, after, segment_ids, positions):
        cfg = self.cfg
        x = jnp.concatenate(
            [RMSNorm(cfg.rms_norm_eps, self.dtype, name="enorm")(after),
             RMSNorm(cfg.rms_norm_eps, self.dtype, name="hnorm")(h)], axis=-1)
        x = _dense(cfg.hidden_size, self.dtype, "eh_proj")(x)
        y, counters = RematLayer(cfg, True, self.attention_block, self.dtype,
                                 name="layer")(x, segment_ids, positions)
        return RMSNorm(cfg.rms_norm_eps, self.dtype, name="final_norm")(y), counters


class Head(nn.Module):
    """The untied output head: float32 logits from operands in ``dtype``."""

    vocab_size: int
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        kernel = self.param("kernel", _normal(), (x.shape[-1], self.vocab_size))
        with jax.named_scope("lm_head"):
            return jnp.einsum("bld,dv->blv", x, kernel.astype(self.dtype),
                              preferred_element_type=jnp.float32)


class Glm4MoeLite(nn.Module):
    """``tokens``, ``segment_ids`` (B, L) int32 -> logits (B, L, vocab)
    float32, the module's logits (None without one) and the routing
    counters.  ``train`` is accepted for the trainer's sake: nothing in the
    model depends on it."""

    cfg: Glm4MoeLiteConfig
    attention_block: int = 512
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, tokens, segment_ids, train: bool = False):
        cfg = self.cfg
        table = self.param("embedding", _normal(),
                           (cfg.vocab_size, cfg.hidden_size))
        head = Head(cfg.vocab_size, self.dtype, name="lm_head")
        with jax.named_scope("embed"):
            h = table.astype(self.dtype)[tokens]
            positions = document_positions(segment_ids)
        per_layer = []
        for i in range(cfg.num_hidden_layers):
            routed = i >= cfg.first_k_dense_replace
            h, counters = RematLayer(cfg, routed, self.attention_block,
                                     self.dtype, name=f"layer_{i}")(
                                         h, segment_ids, positions)
            if routed:
                per_layer.append(counters)
        with jax.named_scope("lm_head"):
            normed = RMSNorm(cfg.rms_norm_eps, self.dtype, name="final_norm")(h)
        logits, logits2 = head(normed), None
        if cfg.num_nextn_predict_layers:
            with jax.named_scope("mtp"):
                with jax.named_scope("embed"):
                    after = table.astype(self.dtype)[
                        next_in_document(tokens, segment_ids)]
                y, counters = PredictionModule(
                    cfg, self.attention_block, self.dtype, name="mtp")(
                        h, after, segment_ids, positions)
                per_layer.append(counters)
                logits2 = head(y)
        return logits, logits2, model_counters(per_layer)
