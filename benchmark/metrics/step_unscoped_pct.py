"""Share of the device time of the traced operations that lies under none
of the five phases: fusions across a boundary, copies, operations without
metadata.  The error bar of the split itself."""

from benchmark import spans


def read(run: dict):
    return spans.device_number(run, "unscoped_pct")
