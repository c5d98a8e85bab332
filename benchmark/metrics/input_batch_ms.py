"""What one batch costs the prefetcher's producer thread: its ``prep_wait +
assemble + h2d`` intervals of ``spans.jsonl`` over its batches (not
``enqueue``, the wait on a full queue).  Batch size over this is the rate
the input side could sustain."""

from benchmark import spans


def read(run: dict):
    return spans.span_number(run, "input_batch_ms")
