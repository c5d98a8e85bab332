"""Self time of the launch record's ``cache`` + ``build`` + ``init`` +
``restore`` stages before the window: the trainer's build and its init
program's run and placement, the compile intervals inside them taken out."""

from benchmark import launch


def read(run: dict):
    return launch.number(run, "setup_build_s")
