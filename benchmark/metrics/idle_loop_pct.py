"""Share of the traced span in which no operation ran on the device while
the train loop was in any stage but ``stall`` (``dispatch``, ``fetch``,
``log``, ``step``, ``profile``): the chip waited for the host loop."""

from benchmark import spans


def read(run: dict):
    return spans.span_number(run, "idle_loop_pct")
