"""Share of the traced span in which no operation ran on the device while
the train loop stood in a ``stall`` interval: the chip waited for a batch."""

from benchmark import spans


def read(run: dict):
    return spans.span_number(run, "idle_input_pct")
