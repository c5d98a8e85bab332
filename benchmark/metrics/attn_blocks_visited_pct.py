"""Share of the causal pairs of blocks that the attention kernels compute in
one step's rows: the blocks from each block of queries' first block of keys
(the first whose documents reach the queries') to the diagonal's, over all
blocks at or below the diagonal, both counted on the host by the prefetcher
from the task's `batch_counters` (`input_attn_blocks_per_step` and
`input_attn_blocks_causal_per_step` in `metrics.jsonl`, the mean over the
window's batches).  Nothing where the program counts neither."""

from benchmark import series


def read(run: dict):
    workdir = run["window"]["workdir"]
    visited = series.last(workdir, "input_attn_blocks_per_step")
    causal = series.last(workdir, "input_attn_blocks_causal_per_step")
    if visited is None or not causal:
        return None
    return 100.0 * visited / causal
