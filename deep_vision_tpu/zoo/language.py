"""Language-model experiments.  ``granite_4_0_h_micro`` is IBM's Granite
4.0-H Micro at its published size: every key of
https://huggingface.co/ibm-granite/granite-4.0-h-micro/blob/main/config.json
that shapes the model (40 layers = 36 Mamba-2 + 4 attention in a period of
ten, hidden 2048, a tied embedding of 100,352 rows: 3.19 B parameters).  It
need not fit one chip; a deployment states its cut (``cli.train --override``,
or the benchmark's configuration file).

The published config gives no optimizer.  Assumed: AdamW 3e-4, b1 0.9,
b2 0.95, eps 1e-8, decay 0.1 on every leaf of rank 2 and more, clip 1.0;
rows of 4,096 tokens (the pre-training stage's length).
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


@register_config("granite_4_0_h_micro")
def granite_4_0_h_micro() -> TrainConfig:
    cfg = TrainConfig(
        name="granite_4_0_h_micro",
        model=None,
        task="language_modeling",
        batch_size=1,  # rows of sequence_length tokens a step
        total_epochs=1,
        optimizer=OptimizerConfig(name="adam", learning_rate=3e-4, b1=0.9,
                                  b2=0.95, eps=1e-8, weight_decay=0.1,
                                  grad_clip_norm=1.0),
        num_classes=GRANITE_4_0_H_MICRO["vocab_size"],
        extra={"architecture": dict(GRANITE_4_0_H_MICRO),
               "sequence_length": 4096},
    )

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
