"""Readings for the limits of the latent-attention cell, all in one process
on the chip: ``readings_moe.py`` with this model's planted faults.

    python3 benchmark/readings_mla.py --workload GLM-4.7-Flash-train-packed8k \\
        --seeds 11,12,... --controls 2 --faults 1 --out chiprun_out/readings_mla.json

Not part of a benchmark run; the driver never calls it.
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

FAULTS = {
    "fault_rotary_on_all_dims": {"rotate": "all"},
    "fault_rope_key_per_head": {"rope_key": "per_head"},
    "fault_no_latent_norm": {"latent_norm": False},
    "fault_no_shared_expert": {"shared": False},
    "fault_scale_1": {"scale": 1.0},
    "fault_second_target_across": {"second": "across"},
    "fault_mtp_weight_0": {"mtp_weight": 0.0},
    "fault_no_balance": {"balance": False},
}

if __name__ == "__main__":
    from benchmark import readings_moe

    readings_moe.FAULTS = FAULTS
    sys.exit(readings_moe.main())
