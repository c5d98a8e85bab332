"""Device time per executed step under the `mtp` scope: the whole
multi-token-prediction module (the next token's embedding, two norms, `W_eh`,
one routed layer, the shared head); all three passes.  An overlay: the
module's operator also counts under `mla_op_ms`, its routed block under
`routed_ffn_ms`."""

from benchmark import mla_scopes


def read(run: dict):
    return mla_scopes.number(run, "mtp")
