"""Granite 4.0-H decoder (``model_type: granitemoehybrid``, dense): Mamba-2
state-space layers with a grouped-query attention layer where
``layer_types`` says so, each followed by a SwiGLU MLP, for training on
packed documents.

Every number comes from the published ``config.json`` (the zoo holds it):

    h = E[tokens] * embedding_multiplier
    h = h + residual_multiplier * mixer(RMSNorm(h))      # mamba | attention
    h = h + residual_multiplier * mlp(RMSNorm(h))
    logits = RMSNorm(h) E^T / logits_scaling             # E tied

The attention layer has no rotary or other position term
(``position_embedding_type: "nope"``) and scales its scores by
``attention_multiplier``.  The Mamba-2 mixer follows Dao & Gu
arXiv:2405.21060: ``in_proj`` to ``z | xBC | dt``, a causal depthwise conv
and SiLU on ``xBC``, the scan (``ops/ssd.py``), ``D`` skip, the gate before
the norm, ``out_proj``.  The conv, the scan's state and the attention mask
all start anew at a document's first token (``segment_ids``).

Each layer is rematerialised in the backward pass (``RematLayer``).  What
is kept between the passes is, a layer, its ``(B, L, hidden)`` input and the
bfloat16 tensors ``KEPT`` names: the outputs of the products against a
weight matrix that the backward pass reads (``in_proj`` and ``out_proj`` of
a Mamba mixer, ``q/k/v`` and ``o_proj`` of attention, the MLP's
``in_proj``), so that no such product runs twice, and the attention
kernel's output with its float32 log-sum-exp a row and head
(``ops/attention.py`` names both in its forward rule), so that the forward
kernel runs once and the backward kernel builds a block's probabilities
from them.  Norms, conv, SiLU, the scan's forward kernel (``ops/ssd.py``: a chunk's ``(chunk, chunk)``
decays and scores live in VMEM inside it and in the backward kernel, and
nowhere else) and the SwiGLU product are computed again.  The kept set costs 532 KB a token for the ten layers of a
pipeline stage at the published widths (527 of them the products'
outputs): 2.0 GiB at 4,096 tokens a step, 4.4 GB at 8,192, which has not
been tried.  Scopes
``embed``, ``mamba`` (``ssd`` inside it, around the scan only),
``attention``, ``mlp`` and ``lm_head`` name the parts in a device trace; a
layer's norm and residual go by its mixer's or its MLP's scope.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from deep_vision_tpu.ops import attention
from deep_vision_tpu.ops.ssd import ssd_scan


@dataclasses.dataclass(frozen=True)
class GraniteHybridConfig:
    vocab_size: int
    hidden_size: int
    layer_types: tuple
    num_attention_heads: int
    num_key_value_heads: int
    shared_intermediate_size: int
    mamba_n_heads: int
    mamba_d_head: int
    mamba_d_state: int
    mamba_d_conv: int
    mamba_expand: int
    mamba_chunk_size: int
    attention_multiplier: float
    embedding_multiplier: float
    residual_multiplier: float
    logits_scaling: float
    rms_norm_eps: float

    # what the equations above assume of a config.json; anything else is
    # another model
    REQUIRED = {"hidden_act": "silu", "position_embedding_type": "nope",
                "normalization_function": "rmsnorm", "num_local_experts": 0,
                "tie_word_embeddings": True, "mamba_n_groups": 1,
                "mamba_conv_bias": True, "mamba_proj_bias": False,
                "attention_bias": False}

    @classmethod
    def from_dict(cls, config: dict) -> "GraniteHybridConfig":
        for key, value in cls.REQUIRED.items():
            if config.get(key) != value:
                raise ValueError(f"{key}: this decoder is written for "
                                 f"{value!r}, the config says {config.get(key)!r}")
        fields = {f.name for f in dataclasses.fields(cls)}
        kw = {k: v for k, v in config.items() if k in fields}
        depth = int(config["num_hidden_layers"])
        if depth > len(config["layer_types"]):
            raise ValueError(f"{depth} layers of {len(config['layer_types'])} "
                             f"layer_types")
        kw["layer_types"] = tuple(config["layer_types"][:depth])
        out = cls(**kw)
        if out.mamba_n_heads * out.mamba_d_head != out.mamba_expand * out.hidden_size:
            raise ValueError("mamba heads x head size differs from expand x hidden")
        return out

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def d_inner(self) -> int:
        return self.mamba_n_heads * self.mamba_d_head


# what a rematerialised layer keeps between the passes beside its input:
# the outputs of these products against a weight matrix, and the attention
# kernel's output and log-sum-exp
KEPT = ("mixer_in_proj", "mixer_out_proj", "q_proj", "k_proj", "v_proj",
        attention.OUT, attention.LSE, "o_proj", "ffn_in_proj")


def _normal():
    return nn.initializers.normal(0.02)


def _a_log(key, shape, dtype=jnp.float32):
    return jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0))


def _dt_bias(key, shape, dtype=jnp.float32):
    """The inverse softplus of a log-uniform step in [1e-3, 0.1]."""
    dt = jnp.exp(jax.random.uniform(key, shape, dtype, math.log(1e-3),
                                    math.log(0.1)))
    return dt + jnp.log(-jnp.expm1(-dt))


def _uniform(bound: float):
    def init(key, shape, dtype=jnp.float32):
        return jax.random.uniform(key, shape, dtype, -bound, bound)
    return init


class RMSNorm(nn.Module):
    eps: float
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],))
        x = x.astype(jnp.float32)
        x = x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + self.eps)
        return (x * scale).astype(self.dtype)


def causal_conv(x, kernel, bias, segment_ids):
    """Depthwise causal conv along the sequence, ``kernel`` (taps, C): tap
    ``k`` reads ``taps - 1 - k`` positions back, and reads zero where that
    position lies before the row's start or in another document."""
    taps = kernel.shape[0]
    out = x * kernel[-1] + bias
    for back in range(1, taps):
        shifted = jnp.pad(x, ((0, 0), (back, 0), (0, 0)))[:, :-back]
        seg = jnp.pad(segment_ids, ((0, 0), (back, 0)),
                      constant_values=-1)[:, :-back]
        out = out + jnp.where((seg == segment_ids)[..., None], shifted, 0.0
                              ) * kernel[taps - 1 - back]
    return out


class MambaMixer(nn.Module):
    cfg: GraniteHybridConfig
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, u, segment_ids):
        cfg, f32 = self.cfg, jnp.float32
        heads, dim, n = cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_state
        inner, conv_dim = cfg.d_inner, cfg.d_inner + 2 * n
        zxbcdt = checkpoint_name(
            nn.Dense(inner + conv_dim + heads, use_bias=False,
                     dtype=self.dtype, kernel_init=_normal(),
                     name="in_proj")(u), "mixer_in_proj")
        z, xbc, dt = jnp.split(zxbcdt, [inner, inner + conv_dim], axis=-1)
        # torch's Conv1d default, which the published implementation leaves:
        # uniform within 1 / sqrt(taps) of zero for a depthwise conv
        conv_init = _uniform(1.0 / math.sqrt(cfg.mamba_d_conv))
        kernel = self.param("conv_kernel", conv_init, (cfg.mamba_d_conv, conv_dim))
        bias = self.param("conv_bias", conv_init, (conv_dim,))
        xbc = nn.silu(causal_conv(xbc.astype(f32), kernel, bias, segment_ids)
                      ).astype(self.dtype)
        x, b, c = jnp.split(xbc, [inner, inner + n], axis=-1)
        x = x.reshape(*x.shape[:2], heads, dim)
        dt = jax.nn.softplus(dt.astype(f32) + self.param("dt_bias", _dt_bias, (heads,)))
        a = -jnp.exp(self.param("A_log", _a_log, (heads,)).astype(f32))
        with jax.named_scope("ssd"):
            y = ssd_scan(x, dt, a, b, c, segment_ids, cfg.mamba_chunk_size)
        y = y + self.param("D", nn.initializers.ones, (heads,))[:, None] * x.astype(f32)
        y = y.reshape(*y.shape[:2], inner) * nn.silu(z.astype(f32))
        y = RMSNorm(cfg.rms_norm_eps, self.dtype, name="norm")(y)
        return checkpoint_name(
            nn.Dense(cfg.hidden_size, use_bias=False, dtype=self.dtype,
                     kernel_init=_normal(), name="out_proj")(y),
            "mixer_out_proj")


class AttentionMixer(nn.Module):
    cfg: GraniteHybridConfig
    attention_block: int = 512
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, u, segment_ids):
        cfg = self.cfg

        def proj(heads, name):
            y = checkpoint_name(
                nn.Dense(heads * cfg.head_dim, use_bias=False, dtype=self.dtype,
                         kernel_init=_normal(), name=name)(u), name)
            return y.reshape(*y.shape[:2], heads, cfg.head_dim)

        out = attention.causal_attention(
            proj(cfg.num_attention_heads, "q_proj"),
            proj(cfg.num_key_value_heads, "k_proj"),
            proj(cfg.num_key_value_heads, "v_proj"),
            segment_ids, cfg.attention_multiplier, self.attention_block)
        return checkpoint_name(
            nn.Dense(cfg.hidden_size, use_bias=False, dtype=self.dtype,
                     kernel_init=_normal(), name="o_proj")(
                         out.reshape(*out.shape[:2], -1)), "o_proj")


class SwiGLU(nn.Module):
    cfg: GraniteHybridConfig
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, u):
        width = self.cfg.shared_intermediate_size
        gate, value = jnp.split(checkpoint_name(
            nn.Dense(2 * width, use_bias=False, dtype=self.dtype,
                     kernel_init=_normal(), name="in_proj")(u),
            "ffn_in_proj"), 2, axis=-1)
        return nn.Dense(self.cfg.hidden_size, use_bias=False, dtype=self.dtype,
                        kernel_init=_normal(), name="out_proj")(nn.silu(gate) * value)


class GraniteLayer(nn.Module):
    cfg: GraniteHybridConfig
    kind: str
    attention_block: int = 512
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, h, segment_ids):
        cfg, r = self.cfg, self.cfg.residual_multiplier
        if self.kind == "mamba":
            mixer = MambaMixer(cfg, self.dtype, name="mixer")
        elif self.kind == "attention":
            mixer = AttentionMixer(cfg, self.attention_block, self.dtype,
                                   name="mixer")
        else:
            raise ValueError(f"unknown layer type {self.kind!r}")
        with jax.named_scope(self.kind):
            u = RMSNorm(cfg.rms_norm_eps, self.dtype, name="mixer_norm")(h)
            h = h + (r * mixer(u, segment_ids)).astype(h.dtype)
        with jax.named_scope("mlp"):
            u = RMSNorm(cfg.rms_norm_eps, self.dtype, name="ffn_norm")(h)
            h = h + (r * SwiGLU(cfg, self.dtype, name="ffn")(u)).astype(h.dtype)
        return h


RematLayer = nn.remat(
    GraniteLayer, policy=jax.checkpoint_policies.save_only_these_names(*KEPT))


class GraniteHybrid(nn.Module):
    """``tokens``, ``segment_ids`` (B, L) int32 -> logits (B, L, vocab)
    float32.  ``train`` is accepted for the trainer's sake: nothing in the
    model depends on it."""

    cfg: GraniteHybridConfig
    attention_block: int = 512
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, tokens, segment_ids, train: bool = False):
        cfg = self.cfg
        table = self.param("embedding", _normal(),
                           (cfg.vocab_size, cfg.hidden_size))
        with jax.named_scope("embed"):
            h = table.astype(self.dtype)[tokens] * jnp.asarray(
                cfg.embedding_multiplier, self.dtype)
        for i, kind in enumerate(cfg.layer_types):
            h = RematLayer(cfg, kind, self.attention_block, self.dtype,
                           name=f"layer_{i}")(h, segment_ids)
        with jax.named_scope("lm_head"):
            h = RMSNorm(cfg.rms_norm_eps, self.dtype, name="final_norm")(h)
            logits = jnp.einsum("bld,vd->blv", h, table.astype(self.dtype),
                                preferred_element_type=jnp.float32)
            return logits / cfg.logits_scaling
