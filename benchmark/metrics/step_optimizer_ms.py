"""Device time per executed step under the ``optimizer`` scope: the finite
check, the update (clip, momentum or Adam, weight decay) and the EMA."""

from benchmark import spans


def read(run: dict):
    return spans.device_number(run, "phase_ms", "optimizer")
