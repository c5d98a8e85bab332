"""Self time of the ``epoch`` calls before the window (the checked steps, a
cell's settling steps), the compile intervals inside them taken out."""

from benchmark import launch


def read(run: dict):
    return launch.number(run, "setup_steps_s")
