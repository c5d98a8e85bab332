"""Device time per executed step under the `mamba` scope: the state-space
layers' norm, projections, conv, scan, gate and residual; forward,
recomputation and backward together."""

from benchmark import lm_scopes


def read(run: dict):
    return lm_scopes.number(run, "mamba")
