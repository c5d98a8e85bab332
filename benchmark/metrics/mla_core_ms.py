"""Device time per executed step under the `mla_core` scope (inside `mla_op`):
the attention op alone, which is its forward and its backward kernel and the
layout passes around them, in every operator."""

from benchmark import mla_scopes


def read(run: dict):
    return mla_scopes.number(run, "mla_core")
