"""Device time per executed step under `moe_experts` (inside `moe`): the held
experts' weights cast to the compute dtype, the grouped products and the
SwiGLU between them; all three passes, divided by the step's executions in
the traced span."""

from benchmark import moe_scopes


def read(run: dict):
    return moe_scopes.number(run, "moe_experts")
