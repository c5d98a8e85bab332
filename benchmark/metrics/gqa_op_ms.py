"""Device time per executed step under the `gqa_op` scope: the attention
operator's norm, projections, head norms, rotary, blocked attention and
residual; all three passes, divided by the step's executions in the traced
span."""

from benchmark import moe_scopes


def read(run: dict):
    return moe_scopes.number(run, "gqa_op")
