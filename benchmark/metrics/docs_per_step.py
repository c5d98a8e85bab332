"""Documents (whole or cut) per batch, counted by the prefetcher from the
task's `batch_counters` (`input_documents_per_step` in `metrics.jsonl`)."""

from benchmark import series


def read(run: dict):
    return series.last(run["window"]["workdir"], "input_documents_per_step")
