"""Device time per executed step under the `ssd` scope: the state-space
scan alone (inside `mamba`); forward, recomputation and backward together."""

from benchmark import lm_scopes


def read(run: dict):
    return lm_scopes.number(run, "ssd")
