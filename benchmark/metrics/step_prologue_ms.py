"""Device time per executed step under the ``prologue`` scope of the
trainer's jitted step: the input's device-side preprocessing (the fused ingest
kernel, or the scale)."""

from benchmark import spans


def read(run: dict):
    return spans.device_number(run, "phase_ms", "prologue")
