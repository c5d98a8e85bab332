"""Device time per executed step under ``jvp(forward)``: the model's
forward pass as the differentiated function runs it."""

from benchmark import spans


def read(run: dict):
    return spans.device_number(run, "phase_ms", "forward")
