"""Backend compiles before the window that the persistent cache did not
serve: 0 on a warm machine, so a reading of ``setup_s`` says of itself
whether it was cold."""

from benchmark import launch


def read(run: dict):
    return launch.number(run, "setup_cache_misses")
