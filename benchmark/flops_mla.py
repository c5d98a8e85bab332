"""Operations and bytes a latent-attention decoder with routed and shared
experts and a multi-token-prediction module requires for a training step,
from the configuration's shapes alone.

Per token, in multiply-accumulates: the five products of every latent
attention operator (``W_qa``, ``W_qb``, ``W_kva``, ``W_kvb``, ``W_o``), the
dense block of the leading layers, and in every routed layer (the module's
among them) the router at its whole width, the shared expert and the routed
experts **with routing taken as even** (a token chooses
``num_experts_per_tok`` of the router's ``published.n_routed_experts``
experts and this chip holds ``n_routed_experts`` of them, so it meets a held
expert ``k x held / width`` times a layer on average); the module's
``W_eh``; the untied head once for the model and once for the module (the
table's gathers are none).  A training step is x 6.  Left out, so that the
count may undercount and never overcount: attention's score and value
products (below), norms, rotary, activations, top-k and sort, the losses,
the optimizer, and everything recomputed in the backward pass.

The attention core's roofline takes the run's own count of the (query, key)
pairs a causal document mask leaves visible (``input_pairs_per_step``:
``sum n (n + 1) / 2`` over a row's documents): six products need them (scores
and values forward; ``dv``, ``dp``, ``dq`` and ``dk`` backward; the scores
the backward kernel computes again are not counted), each ``2 x pairs x
head width x heads`` operations, in every operator, against the bf16 peak;
and against the bandwidth ``q``, ``k``, ``v``, the output, ``do``, ``dq``,
``dk`` and ``dv`` once each in the compute dtype.  The pairs are what the
mask leaves visible and not the blocks a kernel visits, so a kernel that
skips blocks no query sees into can never read over 100%.

    python -m benchmark.flops_mla benchmark/configs/GLM-4.7-Flash.json
"""

from __future__ import annotations

DTYPE_BYTES = {"float32": 4, "bfloat16": 2}
CORE_PRODUCTS = 6   # two forward, four backward
CORE_TENSORS = 8    # q, k, v, out, do, dq, dk, dv


def applies(config: dict) -> bool:
    """Whether the configuration is of a latent-attention model."""
    return "kv_lora_rank" in config


def router_width(config: dict) -> int:
    return int(config.get("published", {}).get("n_routed_experts",
                                               config["n_routed_experts"]))


def head_width(config: dict) -> int:
    return int(config["qk_nope_head_dim"]) + int(config["qk_rope_head_dim"])


def operator_macs(config: dict) -> int:
    hidden, heads = int(config["hidden_size"]), int(config["num_attention_heads"])
    q_rank, kv_rank = int(config["q_lora_rank"]), int(config["kv_lora_rank"])
    nope, rope, v = (int(config["qk_nope_head_dim"]), int(config["qk_rope_head_dim"]),
                     int(config["v_head_dim"]))
    return (hidden * q_rank + q_rank * heads * (nope + rope)
            + hidden * (kv_rank + rope) + kv_rank * heads * (nope + v)
            + heads * v * hidden)


def expert_macs(config: dict) -> int:
    """One expert (routed or shared), one token."""
    return 3 * int(config["hidden_size"]) * int(config["moe_intermediate_size"])


def routed_layer_macs(config: dict) -> float:
    """Operator, router, shared expert and held experts a token, routing
    taken as even."""
    met = (int(config["num_experts_per_tok"]) * int(config["n_routed_experts"])
           / router_width(config))
    return (operator_macs(config) + int(config["hidden_size"]) * router_width(config)
            + int(config["n_shared_experts"]) * expert_macs(config)
            + met * expert_macs(config))


def dense_layer_macs(config: dict) -> int:
    return operator_macs(config) + 3 * int(config["hidden_size"]) * int(
        config["intermediate_size"])


def modules(config: dict) -> int:
    return int(config["num_nextn_predict_layers"])


def operators(config: dict) -> int:
    """Latent attention operators a step runs, the modules' among them."""
    return int(config["num_hidden_layers"]) + modules(config)


def matmul_macs_per_token(config: dict) -> int:
    hidden = int(config["hidden_size"])
    dense = min(int(config["first_k_dense_replace"]), int(config["num_hidden_layers"]))
    routed = operators(config) - dense
    head = int(config["vocab_size"]) * hidden
    total = (dense * dense_layer_macs(config) + routed * routed_layer_macs(config)
             + modules(config) * 2 * hidden * hidden + (1 + modules(config)) * head)
    return int(round(total))


def train_flops_per_sequence(config: dict) -> int:
    return 6 * int(config["sequence_length"]) * matmul_macs_per_token(config)


def core_train_flops(config: dict, pairs: float) -> float:
    """``pairs``: visible (query, key) pairs of one step's rows."""
    return (CORE_PRODUCTS * 2.0 * pairs * head_width(config)
            * int(config["num_attention_heads"]) * operators(config))


def core_train_bytes(config: dict) -> float:
    tokens = int(config["sequence_length"]) * int(config["batch_size"])
    return (float(CORE_TENSORS) * tokens * int(config["num_attention_heads"])
            * head_width(config) * DTYPE_BYTES[config["compute_dtype"]]
            * operators(config))


def core_roofline_seconds(config: dict, pairs: float, peaks: dict) -> tuple:
    """The least time the chip could take for the attention cores of a step
    whose rows leave ``pairs`` pairs visible, and which of the two bounds it."""
    by_flops = core_train_flops(config, pairs) / peaks["bf16_flops_per_s"]
    by_bytes = core_train_bytes(config) / peaks["hbm_bytes_per_s"]
    return max(by_flops, by_bytes), "bytes" if by_bytes > by_flops else "flops"


if __name__ == "__main__":
    import json
    import sys

    with open(sys.argv[1]) as f:
        cfg = json.load(f)
    whole = cfg["sequence_length"] * (cfg["sequence_length"] + 1) // 2
    print(json.dumps({
        "matmul_macs_per_token": matmul_macs_per_token(cfg),
        "train_flops_per_image": train_flops_per_sequence(cfg),
        "core_train_flops_one_document": core_train_flops(cfg, whole),
        "core_train_bytes_per_step": core_train_bytes(cfg),
    }, indent=1))
