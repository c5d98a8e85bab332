"""The expert products' share of their roofline: the least time the chip could
take for them (the larger of 6 x assignments x 3 x hidden x expert width over
the bf16 peak and their bytes over the bandwidth, `benchmark/flops_moe.py`,
with the `moe_assignments` the run logged inside the traced steps, no
recomputation counted)
divided by the device time under `moe_experts`, which holds the
recomputation too."""

from benchmark import moe_scopes


def read(run: dict):
    return moe_scopes.number(run, "moe_experts_roofline_pct")
