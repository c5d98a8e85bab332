"""Device time per executed step under the `dense_ffn` scope: the leading dense
layer's norm, SwiGLU and residual; all three passes, divided by the step's
executions in the traced span."""

from benchmark import moe_scopes


def read(run: dict):
    return moe_scopes.number(run, "dense_ffn")
