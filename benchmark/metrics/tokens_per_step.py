"""Tokens handed to the train step per batch, counted by the prefetcher from
the task's `batch_counters` (`input_tokens_per_step` in `metrics.jsonl`)."""

from benchmark import series


def read(run: dict):
    return series.last(run["window"]["workdir"], "input_tokens_per_step")
