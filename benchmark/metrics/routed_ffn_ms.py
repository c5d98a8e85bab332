"""Device time per executed step under the `routed_ffn` scope: every routed
block (norm, router, top-k, sort, gathers, the held experts' grouped products,
the shared expert, the residual), the prediction module's among them; all
three passes."""

from benchmark import mla_scopes


def read(run: dict):
    return mla_scopes.number(run, "routed_ffn")
