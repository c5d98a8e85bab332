"""Traffic for language-model training cells: a pool of host batches of
packed documents made from the seed, in the format the program's text
loader yields (``deep_vision_tpu/data/text.py``: ``tokens``, ``targets``,
``segment_ids``, ``loss_weights``, each ``(sequences_per_step, L)``).

Document lengths are lognormal (``length_median``, ``length_sigma``) clipped
to ``length_clip``; token ids are Zipf-distributed (``zipf_exponent``) over
ids 1..vocab-1 of the chip's slice of the vocabulary, 0 is the end-of-text
id after each document.  The documents are laid end to end and cut into rows
with no padding: the document a row's end falls in is cut there and goes on
as the next row's first.  Every seed gives the same shapes and the same
number of tokens, only other documents.

The packing is written out here and not imported from the program, so that
the reference's inputs do not depend on the code under test; a test holds
the two packers to the same rows.
"""

from __future__ import annotations

import numpy as np

EOS = 0


def documents(traffic: dict, vocab: int, n_tokens: int, rng) -> list:
    lo, hi = traffic["length_clip"]
    ranks = np.arange(1, vocab, dtype=np.float64)
    p = ranks ** -float(traffic["zipf_exponent"])
    p /= p.sum()
    docs, total = [], 0
    while total < n_tokens:
        n = int(np.clip(rng.lognormal(np.log(traffic["length_median"]),
                                      traffic["length_sigma"]), lo, hi))
        docs.append(rng.choice(vocab - 1, size=n, p=p).astype(np.int32) + 1)
        total += n + 1
    return docs


def pack(docs: list, length: int, rows: int) -> dict:
    stream = np.concatenate([np.append(d, EOS) for d in docs])[: rows * length]
    first = np.zeros(rows * length, bool)
    starts = np.cumsum([0] + [len(d) + 1 for d in docs[:-1]])
    first[starts[starts < rows * length]] = True
    tokens = stream.reshape(rows, length).astype(np.int32)
    first = first.reshape(rows, length)
    first[:, 0] = True  # a cut document goes on as the row's first
    segment_ids = (np.cumsum(first, axis=1) - 1).astype(np.int32)
    weights = np.ones((rows, length), np.float32)
    weights[:, :-1] -= first[:, 1:]
    weights[:, -1] = 0.0
    targets = np.concatenate([tokens[:, 1:], tokens[:, -1:]], axis=1)
    return {"tokens": tokens, "targets": targets, "segment_ids": segment_ids,
            "loss_weights": weights}


def make_pool(config: dict, traffic: dict, seed: int) -> list[dict]:
    rng = np.random.default_rng(int(seed))
    length = int(config["sequence_length"])
    per_step, batches = int(config["batch_size"]), int(traffic["pool_batches"])
    rows = per_step * batches
    docs = documents(traffic, int(config["vocab_size"]), rows * length, rng)
    packed = pack(docs, length, rows)
    return [{k: v[i * per_step:(i + 1) * per_step] for k, v in packed.items()}
            for i in range(batches)]
