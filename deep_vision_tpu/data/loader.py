"""Host-side batching + device prefetch.

Replaces torch ``DataLoader(num_workers=16)`` (ResNet/pytorch/train.py:229-234)
and ``tf.data`` prefetch/AUTOTUNE (YOLO/tensorflow/train.py:265-272) with
numpy batching plus a background thread that ``device_put``s ahead of the
compute stream (double buffering): while step N runs on the TPU, batch N+1 is
already being transferred H2D, so HBM never waits on the host.
"""

from __future__ import annotations

from typing import Any, Callable, Iterator

import numpy as np


def pad_eval_indices(idx: np.ndarray, start: int, batch_size: int
                     ) -> tuple[np.ndarray, np.ndarray, int]:
    """Static-shape eval padding, shared by every loader: slice
    ``idx[start:start+batch_size]``, pad a short tail by repeating the
    first index, and return ``(sel, weight, n_real)`` where ``weight`` is
    the 0/1 mask tasks use to ignore the filler rows."""
    sel = idx[start:start + batch_size]
    n_real = len(sel)
    if 0 < n_real < batch_size:
        sel = np.concatenate([sel, np.repeat(idx[:1], batch_size - n_real)])
    weight = np.zeros(batch_size, np.float32)
    weight[:n_real] = 1.0
    return sel, weight, n_real


# -- worker-side state for PreppedSampleLoader pools (one dict per worker
# process; the 0-worker path calls PREPARE inline with the same per-item
# rng, so pooled and sequential iteration yield IDENTICAL batches) -------
_PREP_WORKER: dict = {}


def _prep_worker_init(cfg: dict):
    _PREP_WORKER.update(cfg)


def _prep_one(args: tuple) -> dict:
    i, epoch = args
    w = _PREP_WORKER
    rng = np.random.default_rng((w["seed"], epoch, int(i)))
    return w["prepare"](w["samples"][i], rng, **w["kwargs"])


class PreppedSampleLoader:
    """Shared machinery for per-sample-prep loaders (detection, pose):
    epoch shuffling, static eval padding, per-item augmentation rng
    derived from ``(seed, epoch, sample_index)`` — deterministic and
    independent of iteration order or worker count — and an optional
    forkserver worker pool with ``prefetch_batches`` async batches in
    flight so worker decode overlaps the consumer's device step.

    Subclasses set ``PREPARE`` to a module-level (picklable) function
    ``prepare(sample, rng, **kwargs)`` and implement ``_prep_kwargs``;
    their own fields must be assigned BEFORE calling ``super().__init__``
    (pool creation snapshots ``_prep_kwargs()``).
    """

    PREPARE: Callable

    def __init__(self, samples, batch_size: int, train: bool, seed: int,
                 num_workers: int = 0, prefetch_batches: int = 2):
        self.samples = samples
        self.batch_size = batch_size
        self.train = train
        self.seed = seed
        self.num_workers = num_workers
        self.prefetch_batches = max(1, prefetch_batches)
        self.epoch = 0
        self._pool = None
        if num_workers > 0:
            import multiprocessing as mp

            # forkserver, NOT fork: the JAX runtime has live threads by
            # loader-construction time (same rationale as ImageNetLoader)
            try:
                ctx = mp.get_context("forkserver")
            except ValueError:
                ctx = mp.get_context("spawn")
            self._pool = ctx.Pool(
                num_workers, initializer=_prep_worker_init,
                initargs=(dict(samples=samples, seed=seed,
                               prepare=type(self).PREPARE,
                               kwargs=self._prep_kwargs()),))

    def _prep_kwargs(self) -> dict:
        raise NotImplementedError

    def set_epoch(self, epoch: int):
        self.epoch = epoch

    def __len__(self) -> int:
        full = len(self.samples) // self.batch_size
        if not self.train and len(self.samples) % self.batch_size:
            return full + 1  # eval covers the FULL set (padded last batch)
        return full

    def _prepare_indexed(self, i: int, epoch: int) -> dict:
        rng = np.random.default_rng((self.seed, epoch, int(i)))
        return type(self).PREPARE(self.samples[i], rng,
                                  **self._prep_kwargs())

    def close(self):
        if self._pool is not None:
            self._pool.terminate()
            # Pool.join has no timeout parameter; terminate() already
            # killed the workers so this only reaps them
            self._pool.join()  # dvtlint: disable=DVT007
            self._pool = None

    def _assemble(self, items: list, weight) -> dict:
        batch = {k: np.stack([it[k] for it in items]) for k in items[0]}
        if not self.train:
            # weight-0 fillers keep the batch shape static; loss metrics
            # and host evaluators honor the mask (shared loader contract)
            batch["weight"] = weight
        return batch

    def __iter__(self) -> Iterator[dict]:
        from collections import deque

        order = np.random.default_rng((self.seed, self.epoch))
        idx = np.arange(len(self.samples))
        if self.train:
            order.shuffle(idx)
        plan = [pad_eval_indices(idx, b * self.batch_size, self.batch_size)
                for b in range(len(self))]
        if self._pool is not None:
            chunk = max(1, self.batch_size // (2 * self.num_workers))
            pending: deque = deque()
            submit = 0
            for b in range(len(plan)):
                while submit < len(plan) and len(pending) < \
                        self.prefetch_batches:
                    args = [(int(i), self.epoch) for i in plan[submit][0]]
                    pending.append(self._pool.map_async(
                        _prep_one, args, chunksize=chunk))
                    submit += 1
                # a hung worker should fail the epoch loudly, not pin
                # the training loop forever
                yield self._assemble(pending.popleft().get(timeout=600.0),
                                     plan[b][1])
        else:
            for sel, weight, _ in plan:
                items = [self._prepare_indexed(int(i), self.epoch)
                         for i in sel]
                yield self._assemble(items, weight)


class ArrayLoader:
    """In-memory dict-of-arrays dataset → shuffled fixed-size batches.

    The epoch-seeded reshuffle mirrors ``DataLoader(shuffle=True)``;
    ``drop_last=True`` keeps shapes static for XLA (no recompiles).
    """

    def __init__(self, data: dict[str, np.ndarray], batch_size: int,
                 shuffle: bool = True, drop_last: bool = True, seed: int = 0,
                 pad_last: bool = False,
                 transform: Callable[[dict, np.random.Generator], dict] | None = None):
        self.data = data
        n = len(next(iter(data.values())))
        for k, v in data.items():
            assert len(v) == n, f"length mismatch on '{k}'"
        self.n = n
        self.batch_size = batch_size
        self.shuffle = shuffle
        self.drop_last = drop_last
        self.pad_last = pad_last
        self.seed = seed
        self.epoch = 0
        self.transform = transform

    def set_epoch(self, epoch: int):
        self.epoch = epoch

    def __len__(self) -> int:
        if self.drop_last:
            return self.n // self.batch_size
        return (self.n + self.batch_size - 1) // self.batch_size

    def __iter__(self) -> Iterator[dict]:
        rng = np.random.default_rng(self.seed + self.epoch)
        idx = rng.permutation(self.n) if self.shuffle else np.arange(self.n)
        end = (self.n // self.batch_size) * self.batch_size if self.drop_last else self.n
        for start in range(0, end, self.batch_size):
            if self.pad_last:
                # static batch size (no XLA recompile, shard-safe) with
                # weight=0 fillers so metrics ignore them
                sel, weight, _ = pad_eval_indices(idx[:end], start,
                                                  self.batch_size)
            else:
                sel = idx[start:start + self.batch_size]
            batch = {k: v[sel] for k, v in self.data.items()}
            if self.pad_last:
                batch["weight"] = weight
            if self.transform is not None:
                batch = self.transform(batch, rng)
            yield batch
