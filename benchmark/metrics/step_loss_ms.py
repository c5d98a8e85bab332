"""Device time per executed step under ``jvp(loss)``: the task's loss on the
model's outputs, ``best_iou`` included; its gradient counts as backward."""

from benchmark import spans


def read(run: dict):
    return spans.device_number(run, "phase_ms", "loss")
