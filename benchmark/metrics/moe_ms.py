"""Device time per executed step under the `moe` scope: the routed layers'
norm, router, top-k, sort, gathers, grouped products and residual; forward,
recomputation and backward together, divided by the executions of the
trainer's jitted step among the traced steps."""

from benchmark import moe_scopes


def read(run: dict):
    return moe_scopes.number(run, "moe")
