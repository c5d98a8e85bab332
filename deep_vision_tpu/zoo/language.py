"""Language-model experiments, each at its published size: every key of the
model's ``config.json`` that shapes it.  None need fit one chip; a
deployment states its cut (``cli.train --override``, or the benchmark's
configuration file).

``granite_4_0_h_micro``: IBM's Granite 4.0-H Micro,
https://huggingface.co/ibm-granite/granite-4.0-h-micro/blob/main/config.json
(40 layers = 36 Mamba-2 + 4 attention in a period of ten, hidden 2048, a
tied embedding of 100,352 rows: 3.19 B parameters); rows of 4,096 tokens
(the pre-training stage's length).

``lfm2_24b_a2b``: Liquid AI's LFM2-24B-A2B,
https://huggingface.co/LiquidAI/LFM2-24B-A2B/blob/main/config.json
(``lfm2_moe``: 40 layers = 30 gated short convs + 10 rotary GQA in a period
of four, hidden 2048, two dense layers then 38 of 64 routed experts, four a
token, a table of 65,536 rows taken as tied: 23.8 B parameters); rows of
8,192 tokens.  ``extra["expert_first"]`` and ``extra["expert_count"]`` say
which experts of every layer this chip holds (default: all 64).
``use_expert_bias`` names a selection bias and the published config and code
give no rule that moves it; assumed: balancing without an auxiliary loss
(Wang et al., arXiv:2408.15664; DeepSeek-V3, arXiv:2412.19437 s2.1.2), a
training step adds ``extra["expert_bias_update_rate"]`` to the bias of every
expert under the mean load and takes it from every one over it.  The rate
is 3e-2, thirty times both papers': theirs is for a router under a warmed-up
rate deep into a run; from a random start at a constant 3e-4 the router
moves by tenths of a score within ten steps, 1e-3 does not hold the loads
and 3e-2 settles them inside thirty steps (PERF.md s6, PR 33).  The model
calls ``ops/moe.py::route`` with its defaults (``scale`` 1, ``eps`` 1e-6):
its config's ``routed_scaling_factor`` is 1 and the model refuses another.

``glm_4_7_flash``: Z.ai's GLM-4.7-Flash,
https://huggingface.co/zai-org/GLM-4.7-Flash/blob/main/config.json
(``glm4_moe_lite``, 30B-A3B: 47 layers of latent attention (MLA: queries
through a latent of 768, keys and values through one of 512 with a single
rotary key of 64 shared by the 20 heads, heads of 192 + 64 and values of
256), hidden 2048, one dense layer of 10,240 then 46 of 64 routed experts of
1,536, four a token, beside one shared expert, an untied table and head of
154,880 rows: 29.94 B parameters, and one multi-token-prediction module of
0.7 B more); rows of 8,192 tokens.  The share of the experts and the
selection bias's rule and rate are as for ``lfm2_24b_a2b`` and for its
reason; the router's weights are ``route(..., scale=1.8, eps=1e-20)`` (sigmoid
scores, ``noaux_tc``: DeepSeek-V3 arXiv:2412.19437 s2.1.2, as transformers'
``glm4_moe`` router is remembered).  The config names one prediction module
and gives neither its form nor its loss weight; assumed: DeepSeek-V3's
(arXiv:2412.19437 s2.2: the next token's embedding and the last layer's
stream, each normed, joined and projected, one decoder layer, the shared
head) at ``extra["mtp_loss_weight"]`` 0.3 (s4.2's first value, which GLM-4.5
arXiv:2508.06471 follows).

No published config gives an optimizer.  Assumed for all: AdamW 3e-4,
b1 0.9, b2 0.95, eps 1e-8, decay 0.1 on every leaf of rank 2 and more,
clip 1.0.
"""

import jax.numpy as jnp

from deep_vision_tpu.core.config import (
    OptimizerConfig,
    TrainConfig,
    register_config,
)

_PERIOD = ["mamba"] * 5 + ["attention"] + ["mamba"] * 4

GRANITE_4_0_H_MICRO = {
    "attention_bias": False, "attention_multiplier": 0.015625,
    "embedding_multiplier": 12, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 8192, "layer_types": _PERIOD * 4,
    "logits_scaling": 8, "mamba_chunk_size": 256, "mamba_conv_bias": True,
    "mamba_d_conv": 4, "mamba_d_head": 64, "mamba_d_state": 128,
    "mamba_expand": 2, "mamba_n_groups": 1, "mamba_n_heads": 64,
    "mamba_proj_bias": False, "max_position_embeddings": 131072,
    "model_type": "granitemoehybrid", "normalization_function": "rmsnorm",
    "num_attention_heads": 32, "num_experts_per_tok": 0,
    "num_hidden_layers": 40, "num_key_value_heads": 8, "num_local_experts": 0,
    "position_embedding_type": "nope", "residual_multiplier": 0.22,
    "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
    "shared_intermediate_size": 8192, "tie_word_embeddings": True,
    "vocab_size": 100352,
}


def _language_config(name: str, architecture: dict, sequence_length: int,
                     **extra) -> TrainConfig:
    """One packed row a step, the optimizer the models are assumed to share."""
    return TrainConfig(
        name=name,
        model=None,
        task="language_modeling",
        batch_size=1,  # rows of sequence_length tokens a step
        total_epochs=1,
        optimizer=OptimizerConfig(name="adam", learning_rate=3e-4, b1=0.9,
                                  b2=0.95, eps=1e-8, weight_decay=0.1,
                                  grad_clip_norm=1.0),
        num_classes=architecture["vocab_size"],
        extra={"architecture": dict(architecture),
               "sequence_length": sequence_length, **extra},
    )


@register_config("granite_4_0_h_micro")
def granite_4_0_h_micro() -> TrainConfig:
    cfg = _language_config("granite_4_0_h_micro", GRANITE_4_0_H_MICRO, 4096)

    def model():
        # built when asked for, so that an override of ``extra`` counts
        from deep_vision_tpu.models.granite_hybrid import (
            GraniteHybrid,
            GraniteHybridConfig,
        )

        return GraniteHybrid(
            GraniteHybridConfig.from_dict(cfg.extra["architecture"]),
            dtype=jnp.bfloat16 if cfg.half_precision else jnp.float32)

    cfg.model = model
    return cfg


_LFM2_PERIOD = ["conv", "conv", "full_attention", "conv"]

LFM2_24B_A2B = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
    "intermediate_size": 11776, "layer_types": _LFM2_PERIOD * 10,
    "max_position_embeddings": 128000, "model_type": "lfm2_moe",
    "moe_intermediate_size": 1536, "norm_eps": 1e-05, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_dense_layers": 2, "num_experts": 64,
    "num_experts_per_tok": 4, "num_hidden_layers": 40,
    "num_key_value_heads": 8,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "routed_scaling_factor": 1, "use_expert_bias": True, "vocab_size": 65536,
}


@register_config("lfm2_24b_a2b")
def lfm2_24b_a2b() -> TrainConfig:
    cfg = _language_config("lfm2_24b_a2b", LFM2_24B_A2B, 8192,
                           expert_first=0, expert_count=None,
                           expert_bias_update_rate=3e-2)

    def model():
        from deep_vision_tpu.models.lfm2_moe import Lfm2Moe, Lfm2MoeConfig

        return Lfm2Moe(
            Lfm2MoeConfig.from_dict(cfg.extra["architecture"],
                                    cfg.extra["expert_first"],
                                    cfg.extra["expert_count"],
                                    cfg.extra["expert_bias_update_rate"]),
            dtype=jnp.bfloat16 if cfg.half_precision else jnp.float32)

    cfg.model = model
    return cfg


GLM_4_7_FLASH = {
    "attention_bias": False, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 10240, "max_position_embeddings": 202752,
    "model_type": "glm4_moe_lite", "moe_intermediate_size": 1536,
    "topk_method": "noaux_tc", "norm_topk_prob": True,
    "num_attention_heads": 20, "n_group": 1, "topk_group": 1,
    "n_routed_experts": 64, "n_shared_experts": 1,
    "routed_scaling_factor": 1.8, "num_experts_per_tok": 4,
    "first_k_dense_replace": 1, "num_hidden_layers": 47,
    "num_key_value_heads": 20, "num_nextn_predict_layers": 1,
    "partial_rotary_factor": 1, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 1000000, "tie_word_embeddings": False, "q_lora_rank": 768,
    "kv_lora_rank": 512, "qk_nope_head_dim": 192, "qk_rope_head_dim": 64,
    "v_head_dim": 256, "vocab_size": 154880,
}


@register_config("glm_4_7_flash")
def glm_4_7_flash() -> TrainConfig:
    cfg = _language_config("glm_4_7_flash", GLM_4_7_FLASH, 8192,
                           expert_first=0, expert_count=None,
                           expert_bias_update_rate=3e-2, mtp_loss_weight=0.3)

    def model():
        from deep_vision_tpu.models.glm4_moe_lite import (
            Glm4MoeLite,
            Glm4MoeLiteConfig,
        )

        return Glm4MoeLite(
            Glm4MoeLiteConfig.from_dict(cfg.extra["architecture"],
                                        cfg.extra["expert_first"],
                                        cfg.extra["expert_count"],
                                        cfg.extra["expert_bias_update_rate"]),
            dtype=jnp.bfloat16 if cfg.half_precision else jnp.float32)

    cfg.model = model
    return cfg
