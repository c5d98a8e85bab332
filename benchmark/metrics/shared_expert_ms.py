"""Device time per executed step under the `shared_expert` scope (inside
`routed_ffn`): the SwiGLU every token passes beside its routed experts, in
every routed block; all three passes."""

from benchmark import mla_scopes


def read(run: dict):
    return mla_scopes.number(run, "shared_expert")
