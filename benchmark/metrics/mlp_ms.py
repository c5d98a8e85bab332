"""Device time per executed step under the `mlp` scope: every layer's norm,
SwiGLU MLP and residual; forward, recomputation and backward together."""

from benchmark import lm_scopes


def read(run: dict):
    return lm_scopes.number(run, "mlp")
