"""Positions of one step that carry a second target (`t + 2` lies in the row and
in the document of `t`), counted on the device by the task and written with
the step's metrics (`train_mtp_targets` in `metrics.jsonl`; the mean over the
steps logged inside the traced ones)."""

from benchmark import mla_scopes


def read(run: dict):
    return mla_scopes.traced_counter(run, "train_mtp_targets")
