"""Device time per executed step under the `mla_op` scope: the latent attention
operators of every layer and of the prediction module (norm, the five weight
products, the latents' norms, rotary, the attention kernels and the layout
around them, the residual); all three passes, divided by the step's
executions in the traced span."""

from benchmark import mla_scopes


def read(run: dict):
    return mla_scopes.number(run, "mla_op")
