"""Bytes shipped host to device per step, counted by the prefetcher
(``input_h2d_bytes_per_step`` in ``metrics.jsonl``); repeats exactly."""

from benchmark import series


def read(run: dict):
    return series.last(run["window"]["workdir"], "input_h2d_bytes_per_step")
