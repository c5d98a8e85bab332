"""Peak device memory on the fullest chip after the window, before the
reference runs: ``peak_bytes_in_use`` + ``peak_bytes_reserved`` of
``memory_stats()`` (``run.memory_peak``).  The two peaks need not fall at
the same moment, so the sum is an upper bound on what was held at once."""


def read(run: dict):
    return run["memory_peak_bytes"] / 2 ** 30
