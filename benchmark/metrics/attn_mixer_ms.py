"""Device time per executed step under the `attention` scope: the
attention layers' norm, projections, blocked attention and residual;
forward, recomputation and backward together."""

from benchmark import lm_scopes


def read(run: dict):
    return lm_scopes.number(run, "attention")
