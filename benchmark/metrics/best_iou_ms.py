"""Device time per executed step under the ``best_iou`` scope of the
detection loss: the ignore mask's IoU maximum, whichever of the Pallas kernel
and the XLA broadcast implements it."""

from benchmark import spans


def read(run: dict):
    return spans.device_number(run, "best_iou_ms")
