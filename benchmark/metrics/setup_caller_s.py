"""``setup_s`` less the launch record's four other times: what ran between
the program's stages before the window (the pool, the benchmark's weight
draws, ``device_get`` of the checked steps' records)."""

from benchmark import launch


def read(run: dict):
    return launch.number(run, "setup_caller_s")
