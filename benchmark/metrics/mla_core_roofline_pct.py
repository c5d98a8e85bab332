"""The attention core's share of its roofline: the least time the chip could take
for the six products over the (query, key) pairs the step's documents leave
visible (`attn_pairs_per_step`; 2 x pairs x 256 x heads operations each, in
every operator) or for `q`, `k`, `v`, the output and the four cotangents once
each, whichever is larger (`benchmark/flops_mla.py`; neither the scores the
backward kernel computes again nor the masked half of a block on the
diagonal is counted), divided by the device time under `mla_core`."""

from benchmark import mla_scopes


def read(run: dict):
    return mla_scopes.number(run, "mla_core_roofline_pct")
