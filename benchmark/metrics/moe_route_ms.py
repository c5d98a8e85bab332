"""Device time per executed step under `moe_route` (inside `moe`): the router's
float32 product, sigmoid, top-k, the sort by expert, both gathers of rows
and the weighted sum; all three passes, divided by the step's executions in
the traced span."""

from benchmark import moe_scopes


def read(run: dict):
    return moe_scopes.number(run, "moe_route")
