"""Packed text for language-model training: variable-length documents
concatenated into fixed rows with no padding.

``pack_documents`` lays the documents end to end, an end-of-text id after
each, and cuts the stream into rows of ``sequence_length``: the document a
row's end falls in is cut there and goes on as the next row's first.  Every
row carries

- ``tokens`` (N, L) int32;
- ``targets`` (N, L) int32, ``tokens`` shifted left by one;
- ``segment_ids`` (N, L) int32, 0 for the row's first document and counting
  up at each document's first token: contiguous and never decreasing, which
  ``ops/ssd.py`` and ``ops/attention.py`` rely on;
- ``loss_weights`` (N, L) float32, 0 at the row's last position and where
  the next position starts another document, else 1.

NumPy only: loader workers import this without JAX.
"""

from __future__ import annotations

import numpy as np


def pack_documents(documents, sequence_length: int, eos_id: int = 0) -> dict:
    """``documents``: an iterable of 1-D integer arrays.  The stream's tail
    that does not fill a row is dropped."""
    length = int(sequence_length)
    pieces, starts, at = [], [], 0
    for doc in documents:
        doc = np.asarray(doc, np.int32)
        starts.append(at)
        pieces += [doc, np.asarray([eos_id], np.int32)]
        at += len(doc) + 1
    rows = at // length
    if rows == 0:
        raise ValueError(f"{at} tokens do not fill one row of {length}")
    tokens = np.concatenate(pieces)[: rows * length].reshape(rows, length)
    first = np.zeros(rows * length, bool)
    first[[s for s in starts if s < rows * length]] = True
    first = first.reshape(rows, length)
    segment_ids = np.cumsum(first, axis=1, dtype=np.int32)
    segment_ids -= segment_ids[:, :1]
    targets = np.concatenate([tokens[:, 1:], tokens[:, -1:]], axis=1)
    loss_weights = np.ones((rows, length), np.float32)
    loss_weights[:, :-1][first[:, 1:]] = 0.0
    loss_weights[:, -1] = 0.0
    return {"tokens": tokens, "targets": targets, "segment_ids": segment_ids,
            "loss_weights": loss_weights}


def synthetic_corpus(n_tokens: int, vocab_size: int, seed: int = 0,
                     median_length: float = 600.0, sigma: float = 1.2,
                     max_length: int = 4096) -> list:
    """Documents for ``--synthetic`` runs, about ``n_tokens`` in all: lengths
    lognormal, ids 1..vocab-1 (0 is the end-of-text id).  Each document
    repeats a short motif of its own under a quarter of noise, which a model
    can learn to copy, so the loss falls."""
    rng = np.random.default_rng(seed)
    docs, total = [], 0
    while total < n_tokens:
        n = int(np.clip(rng.lognormal(np.log(median_length), sigma), 8, max_length))
        motif = rng.integers(1, vocab_size, rng.integers(2, 9))
        doc = motif[np.arange(n) % len(motif)]
        noise = rng.random(n) < 0.25
        doc[noise] = rng.integers(1, vocab_size, int(noise.sum()))
        docs.append(doc.astype(np.int32))
        total += n + 1
    return docs
