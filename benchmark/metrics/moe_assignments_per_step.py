"""Assignments (token, expert) that fell on the experts this chip holds, summed
over the expert layers of one step (not divided by anything: the mean over
the steps logged inside the traced ones), counted on the device by the model
and written with the step's metrics (`train_moe_assignments` in
`metrics.jsonl`)."""

from benchmark import moe_scopes


def read(run: dict):
    return moe_scopes.traced_counter(run, "train_moe_assignments")
