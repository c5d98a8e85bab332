"""Device time per executed step under any ``transpose(...)`` component:
the backward pass of the model and of the loss."""

from benchmark import spans


def read(run: dict):
    return spans.device_number(run, "phase_ms", "backward")
