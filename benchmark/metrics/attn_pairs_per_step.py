"""(query, key) pairs a causal document mask leaves visible in one step's rows
(the sum over the documents of n (n + 1) / 2), counted on the host by the
prefetcher from the task's `batch_counters` (`input_pairs_per_step` in
`metrics.jsonl`, the mean over the window's batches); reported where the
configuration is of a latent-attention model."""

from benchmark import mla_scopes


def read(run: dict):
    return mla_scopes.pairs_per_step(run)
