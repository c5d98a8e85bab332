"""Operations and bytes a gated-conv / attention decoder with routed
experts requires for a training step, from the configuration's shapes alone.

Per token, in multiply-accumulates: every dense product of every layer, the
router at its whole width, the tied output head (the embedding's gather is
none) and the routed experts **with routing taken as even**: a token chooses
``num_experts_per_tok`` of the router's ``published.num_experts`` experts
and this chip holds ``num_experts`` of them, so it meets a held expert
``k x held / width`` times a layer on average.  A training step is x 6 (2
operations a multiply-accumulate forward, twice that for the two backward
products).  Left out, so that the count may undercount and never overcount:
attention's score and value products, the depthwise conv, norms, rotary,
activations, top-k and sort, the loss, the optimizer, and everything
recomputed in the backward pass.

The expert products' roofline takes the run's own count of assignments
``A`` (``moe_assignments``, summed over the expert layers): ``6 x A x 3 x
hidden x moe_intermediate_size`` operations against the bf16 peak, and
against the bandwidth, a layer, the held experts' weights three times in
their stored dtype (read forward, read backward, their gradient written)
plus the assignments' rows in and out of each pass (``4 x A x hidden``
values in the compute dtype).  No recomputation is counted.

    python -m benchmark.flops_moe benchmark/configs/LFM2-24B-A2B.json
"""

from __future__ import annotations

DTYPE_BYTES = {"float32": 4, "bfloat16": 2}


def layer_types(config: dict) -> list:
    return list(config["layer_types"])[: int(config["num_hidden_layers"])]


def router_width(config: dict) -> int:
    return int(config.get("published", {}).get("num_experts",
                                               config["num_experts"]))


def conv_macs(config: dict) -> int:
    hidden = int(config["hidden_size"])
    return hidden * 3 * hidden + hidden * hidden


def attention_macs(config: dict) -> int:
    hidden = int(config["hidden_size"])
    kv = (hidden // int(config["num_attention_heads"])
          * int(config["num_key_value_heads"]))
    return 2 * hidden * hidden + 2 * hidden * kv


def dense_ffn_macs(config: dict) -> int:
    return 3 * int(config["hidden_size"]) * int(config["intermediate_size"])


def expert_macs(config: dict) -> int:
    """One expert, one token."""
    return 3 * int(config["hidden_size"]) * int(config["moe_intermediate_size"])


def routed_macs(config: dict) -> float:
    """Router and held experts a token a layer, routing taken as even."""
    met = (int(config["num_experts_per_tok"]) * int(config["num_experts"])
           / router_width(config))
    return int(config["hidden_size"]) * router_width(config) + met * expert_macs(config)


def expert_layers(config: dict) -> int:
    return max(len(layer_types(config)) - int(config["num_dense_layers"]), 0)


def matmul_macs_per_token(config: dict) -> int:
    kinds = layer_types(config)
    dense = min(int(config["num_dense_layers"]), len(kinds))
    total = (kinds.count("conv") * conv_macs(config)
             + kinds.count("full_attention") * attention_macs(config)
             + dense * dense_ffn_macs(config)
             + expert_layers(config) * routed_macs(config)
             + int(config["vocab_size"]) * int(config["hidden_size"]))
    return int(round(total))


def train_flops_per_sequence(config: dict) -> int:
    return 6 * int(config["sequence_length"]) * matmul_macs_per_token(config)


def experts_train_flops(config: dict, assignments: float) -> float:
    return 6.0 * assignments * expert_macs(config)


def experts_train_bytes(config: dict, assignments: float) -> float:
    weights = (int(config["num_experts"]) * expert_macs(config)
               * DTYPE_BYTES[config["param_dtype"]])
    rows = (4.0 * assignments * int(config["hidden_size"])
            * DTYPE_BYTES[config["compute_dtype"]])
    return 3.0 * weights * expert_layers(config) + rows


def experts_roofline_seconds(config: dict, assignments: float, peaks: dict) -> tuple:
    """The least time the chip could take for the expert products of a step
    with ``assignments`` held assignments, and which of the two bounds it."""
    by_flops = experts_train_flops(config, assignments) / peaks["bf16_flops_per_s"]
    by_bytes = experts_train_bytes(config, assignments) / peaks["hbm_bytes_per_s"]
    return max(by_flops, by_bytes), "bytes" if by_bytes > by_flops else "flops"


if __name__ == "__main__":
    import json
    import sys

    with open(sys.argv[1]) as f:
        cfg = json.load(f)
    even = (cfg["sequence_length"] * cfg["batch_size"] * cfg["num_experts_per_tok"]
            * cfg["num_experts"] / router_width(cfg) * expert_layers(cfg))
    print(json.dumps({
        "matmul_macs_per_token": matmul_macs_per_token(cfg),
        "train_flops_per_image": train_flops_per_sequence(cfg),
        "even_assignments_per_step": even,
        "experts_train_flops_per_step": experts_train_flops(cfg, even),
        "experts_train_bytes_per_step": experts_train_bytes(cfg, even),
    }, indent=1))
