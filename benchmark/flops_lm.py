"""Operations and bytes a hybrid state-space decoder's training step
requires, from the configuration's shapes alone.

Per token, in multiply-accumulates: every dense product of every layer and
the tied output head (the embedding's gather is none), plus the state-space
recurrence **as its equations state it**, whatever implements it -- per
Mamba-2 layer and token the decay of the state, the update ``dt x (outer)
B`` and the read-out ``H C``: 3 x d_inner x d_state.  A training step is 2
operations a multiply-accumulate for the forward pass and twice that again
for the two backward products: x 6.  Left out, so that the count may
undercount and never overcount: attention's score and value products (at
most ``2 x L x hidden / 2`` = 8.4 M a token at 4,096 causal positions), the
depthwise conv, norms, activations, the loss, the optimizer, and everything
recomputed in the backward pass.

The scan's bytes are what the recurrence has to move once: it reads ``x``,
``B``, ``C`` (the compute dtype, 2 bytes) and ``dt`` (float32), writes ``y``
(float32, as the state is), and the backward pass moves the same shapes
again as gradients.

    python -m benchmark.flops_lm benchmark/configs/granite-4.0-h-micro.json
"""

from __future__ import annotations


def layer_types(config: dict) -> list:
    return list(config["layer_types"])[: int(config["num_hidden_layers"])]


def d_inner(config: dict) -> int:
    return int(config["mamba_n_heads"]) * int(config["mamba_d_head"])


def mlp_macs(config: dict) -> int:
    return 3 * int(config["hidden_size"]) * int(config["shared_intermediate_size"])


def mamba_matmul_macs(config: dict) -> int:
    hidden, inner = int(config["hidden_size"]), d_inner(config)
    in_proj = 2 * inner + 2 * int(config["mamba_d_state"]) + int(config["mamba_n_heads"])
    return hidden * in_proj + inner * hidden


def attention_matmul_macs(config: dict) -> int:
    hidden = int(config["hidden_size"])
    kv = (hidden // int(config["num_attention_heads"])
          * int(config["num_key_value_heads"]))
    return 2 * hidden * hidden + 2 * hidden * kv


def matmul_macs_per_token(config: dict) -> int:
    kinds = layer_types(config)
    return (kinds.count("mamba") * mamba_matmul_macs(config)
            + kinds.count("attention") * attention_matmul_macs(config)
            + len(kinds) * mlp_macs(config)
            + int(config["vocab_size"]) * int(config["hidden_size"]))


def scan_macs_per_token(config: dict) -> int:
    """All Mamba-2 layers of the model."""
    return (layer_types(config).count("mamba")
            * 3 * d_inner(config) * int(config["mamba_d_state"]))


def train_flops_per_sequence(config: dict) -> int:
    return 6 * int(config["sequence_length"]) * (
        matmul_macs_per_token(config) + scan_macs_per_token(config))


def scan_train_flops(config: dict, tokens: int) -> int:
    return 6 * tokens * scan_macs_per_token(config)


def scan_train_bytes(config: dict, tokens: int) -> int:
    """Forward reads x, B, C, dt and writes y; backward the same again."""
    inner, n = d_inner(config), int(config["mamba_d_state"])
    forward = 2 * (inner + 2 * n) + 4 * int(config["mamba_n_heads"]) + 4 * inner
    return 2 * forward * tokens * layer_types(config).count("mamba")


def scan_roofline_seconds(config: dict, tokens: int, peaks: dict) -> tuple:
    """The least time the chip could take for the scans of ``tokens``
    tokens of training, and which of the two bounds it."""
    by_flops = scan_train_flops(config, tokens) / peaks["bf16_flops_per_s"]
    by_bytes = scan_train_bytes(config, tokens) / peaks["hbm_bytes_per_s"]
    return max(by_flops, by_bytes), "bytes" if by_bytes > by_flops else "flops"


if __name__ == "__main__":
    import json
    import sys

    with open(sys.argv[1]) as f:
        cfg = json.load(f)
    print(json.dumps({
        "matmul_macs_per_token": matmul_macs_per_token(cfg),
        "scan_macs_per_token": scan_macs_per_token(cfg),
        "train_flops_per_image": train_flops_per_sequence(cfg),
        "scan_train_flops_per_step": scan_train_flops(cfg, cfg["sequence_length"]),
        "scan_train_bytes_per_step": scan_train_bytes(cfg, cfg["sequence_length"]),
    }, indent=1))
