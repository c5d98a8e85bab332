"""Self time of the launch record's ``outside`` + ``import`` + ``backend``
stages (``launch.jsonl``): the process's start to the package's first call,
and the runtime's start where the program is the one that starts it: the
machine's and the harness's share of ``setup_s``."""

from benchmark import launch


def read(run: dict):
    return launch.number(run, "setup_outside_s")
