"""The scan's share of its roofline: the least time the chip could take for
the recurrence as its equations state it (the larger of its operations over
the bf16 peak and its bytes over the bandwidth, `benchmark/flops_lm.py`,
from shapes alone, forward and backward once each) over the device time
under the `ssd` scope, which also holds the recomputation."""

from benchmark import lm_scopes


def read(run: dict):
    return lm_scopes.number(run, "ssd_roofline_pct")
