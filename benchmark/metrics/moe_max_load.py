"""Rows of the fullest held expert in one step, the largest over the expert
layers (the mean over the steps logged inside the traced ones; the mean load
is tokens x experts per token / the router's width), counted on the device
by the model (`train_moe_max_load` in `metrics.jsonl`)."""

from benchmark import moe_scopes


def read(run: dict):
    return moe_scopes.traced_counter(run, "train_moe_max_load")
