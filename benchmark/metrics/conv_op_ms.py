"""Device time per executed step under the `conv_op` scope: the gated
short-conv operators' norm, projections, gates, three-tap conv and residual;
all three passes, divided by the step's executions in the traced span."""

from benchmark import moe_scopes


def read(run: dict):
    return moe_scopes.number(run, "conv_op")
