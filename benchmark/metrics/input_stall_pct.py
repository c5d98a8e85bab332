"""Share of the epoch the consumer waited on the prefetcher's queue:
``input_stall_frac`` of the ``DevicePrefetcher`` stats, as the trainer logs
them to ``metrics.jsonl`` at the end of the window's epoch."""

from benchmark import series


def read(run: dict):
    frac = series.last(run["window"]["workdir"], "input_stall_frac")
    return None if frac is None else 100.0 * frac
