"""ImageNet (ILSVRC2012) input pipeline.

Dataset semantics mirror ``ImageNet2012Dataset``
(ResNet/pytorch/data_load.py:14-69): a FLAT directory of JPEGs whose label is
the synset prefix of the filename ("n02708093_7537.JPEG"), mapped to an index
via the metadata file (one "synset name..." line per class —
Datasets/ILSVRC2012/imagenet_2012_metadata.txt).

TPU-first loader design (SURVEY §7 hard-part 1 — keep the chips fed from
host Python):
- files are sharded per HOST (``jax.process_index``) so a multi-host pod
  never reads the same image twice per epoch;
- a multiprocess worker pool decodes+augments (the torch
  ``DataLoader(num_workers=16)`` role, ResNet/pytorch/train.py:229-234);
- batches flow through ``data.pipeline.DevicePrefetcher`` for staged H2D.
"""

from __future__ import annotations

import os
from typing import Iterator

import numpy as np

from deep_vision_tpu.data import transforms as T


def load_synset_index(labels_file: str) -> dict[str, int]:
    """synset → class index, line order = index (reference :33-44)."""
    mapping: dict[str, int] = {}
    with open(labels_file) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue  # blank lines don't consume an index
            # split on ANY whitespace: the reference metadata file is
            # tab-separated ("n01440764\ttench, Tinca tinca")
            mapping[line.split()[0]] = len(mapping)
    return mapping


def _decode(path: str, draft_size: int | None = None) -> np.ndarray:
    from PIL import Image

    with Image.open(path) as im:
        if draft_size is not None:
            # JPEG DCT-domain downscale during decode (1/2, 1/4, 1/8):
            # large photos decode several× faster; PIL guarantees the
            # result stays ≥ the requested size, so rescale() still works
            im.draft("RGB", (draft_size, draft_size))
        return np.asarray(im.convert("RGB"))  # drops alpha, CMYK→RGB


def _decode_bytes(data: bytes, draft_size: int | None = None,
                  fast: bool = False) -> np.ndarray:
    import io

    from PIL import Image

    if fast:
        # cv2 JPEG decode is ~20% faster end-to-end and bit-identical to
        # PIL's (both libjpeg-turbo).  Only safe for SANITIZED sources
        # (prepare_imagenet re-encodes everything to clean RGB JPEG at
        # build time) — cv2 silently mis-decodes CMYK, so the folder path
        # stays on PIL.  A cheap PIL header peek picks the DCT half-size
        # decode when it still covers the resize target (draft semantics).
        from deep_vision_tpu.data.transforms import _cv2

        if _cv2 is not None:
            flag = _cv2.IMREAD_COLOR
            if draft_size is not None:
                with Image.open(io.BytesIO(data)) as im:  # header only
                    w, h = im.size
                # deepest DCT reduction that still covers the resize
                # target — the full 1/2–1/8 ladder PIL's draft offers
                for shift, reduced in ((3, _cv2.IMREAD_REDUCED_COLOR_8),
                                       (2, _cv2.IMREAD_REDUCED_COLOR_4),
                                       (1, _cv2.IMREAD_REDUCED_COLOR_2)):
                    if min(w, h) >> shift >= draft_size:
                        flag = reduced
                        break
            img = _cv2.imdecode(np.frombuffer(data, np.uint8), flag)
            if img is not None and img.ndim == 3 and img.shape[2] == 3:
                return _cv2.cvtColor(img, _cv2.COLOR_BGR2RGB)
            # undecodable by cv2: fall through to the robust PIL path
    with Image.open(io.BytesIO(data)) as im:
        if draft_size is not None:
            im.draft("RGB", (draft_size, draft_size))
        return np.asarray(im.convert("RGB"))


class ImageNetRecords:
    """Random-access view over classification dvrec shards (the consuming
    side of ``prepare_data imagenet`` — the reference's TFRecord trainer
    path, ResNet/tensorflow/train.py:178-214).

    Construction scans shard HEADERS once (seeking over payloads) to build
    an (path, offset, length, label) index; reads are then positioned
    single-payload I/O, so the same multiprocess decode pool as the folder
    loader parallelizes cleanly."""

    def __init__(self, root: str, split: str):
        import json
        import struct

        from deep_vision_tpu.data.records import list_shards

        u32 = struct.Struct("<I")
        # entry = (path, offset, length, shape|None): shape set for
        # train-ready raw-uint8 payloads (prepare_data --store raw), None
        # for JPEG payloads that decode at read time
        self.entries: list[tuple[str, int, int, tuple | None]] = []
        labels: list[int] = []
        shards = list_shards(root, split)
        if not shards:
            raise FileNotFoundError(f"no {split}-*.dvrec under {root}")
        for path in shards:
            with open(path, "rb") as f:
                while True:
                    raw = f.read(4)
                    if len(raw) < 4:
                        break
                    (hlen,) = u32.unpack(raw)
                    header = json.loads(f.read(hlen))
                    (plen,) = u32.unpack(f.read(4))
                    off = f.tell()
                    f.seek(plen, 1)  # skip payload
                    shape = tuple(header["shape"]) \
                        if header.get("enc") == "raw" else None
                    self.entries.append((path, off, plen, shape))
                    labels.append(int(header["label"]))
        self.labels = np.asarray(labels, np.int32)

    def __len__(self) -> int:
        return len(self.entries)


# worker-local fd cache: positioned reads reuse one open fd per shard.
# Capped (LRU-ish) so 1024-shard datasets never approach the per-process
# open-file limit; evicted fds are closed, reopening is cheap
_FDS: dict = {}
_FDS_MAX = 64


def _get_fd(path: str):
    f = _FDS.get(path)
    if f is None:
        while len(_FDS) >= _FDS_MAX:
            # evict the least-recently-used (dicts iterate in insertion
            # order; hits below re-insert, so the front is the coldest)
            old = _FDS.pop(next(iter(_FDS)))
            old.close()
        f = _FDS[path] = open(path, "rb")
    else:  # move-to-end on hit → LRU order holds under round-robin reads
        _FDS[path] = _FDS.pop(path)
    return f


def _pread(path: str, off: int, length: int) -> bytes:
    f = _get_fd(path)
    f.seek(off)
    return f.read(length)


def _close_fds():
    while _FDS:
        _, f = _FDS.popitem()
        f.close()


class ImageNetFolder:
    """Flat-folder dataset: index → (decoded RGB uint8 HWC, label)."""

    def __init__(self, root_dir: str, labels_file: str):
        self.root_dir = root_dir
        self.files = sorted(
            f for f in os.listdir(root_dir)
            if os.path.isfile(os.path.join(root_dir, f)))
        label_to_idx = load_synset_index(labels_file)
        # filename prefix before the first '_' is the synset (reference :60-63)
        self.labels = np.array(
            [label_to_idx[f.split("_")[0]] for f in self.files], np.int32)

    def __len__(self) -> int:
        return len(self.files)

    def read(self, i: int) -> tuple[np.ndarray, int]:
        return _decode(os.path.join(self.root_dir, self.files[i])), int(self.labels[i])


# -- worker-side state (initialized once per worker PROCESS; never shared
# between loaders in-process — the 0-worker path passes cfg explicitly) -----
_WORKER: dict = {}


def _worker_init(cfg: dict):
    _WORKER.update(cfg)


def _load_one(cfg: dict, i: int, seed: int) -> tuple[np.ndarray, np.int32]:
    # draft (DCT-domain downscale) only on the fast uint8 path — the
    # --host-normalize path promises reference-exact decode semantics
    draft = cfg["resize"] if cfg.get("device_normalize") else None
    if "entries" in cfg:  # dvrec shards: positioned read (+ decode)
        path, off, plen, shape = cfg["entries"][i]
        if shape is not None:
            # train-ready raw payload: no decode at all — frombuffer and
            # go straight to crop/flip (the rescale below is a no-op when
            # the build-time short side matches cfg["resize"])
            img = np.frombuffer(_pread(path, off, plen),
                                np.uint8).reshape(shape)
        else:
            # cv2 fast decode: records are sanitized RGB JPEG at build
            # time, and it's gated (like draft) to the device-normalize
            # path — the host-normalize/tf paths keep their
            # reference-exact PIL decode
            img = _decode_bytes(_pread(path, off, plen), draft_size=draft,
                                fast=bool(cfg.get("device_normalize")))
    else:
        img = _decode(os.path.join(cfg["root_dir"], cfg["files"][i]),
                      draft_size=draft)
    if cfg.get("preprocessing") == "tf":
        # TF "ResNet preprocessing" variant (mean-centered 0-255 floats) —
        # host-only, incompatible with the device-normalize split
        if cfg["train"]:
            rng = np.random.default_rng(seed)
            x = T.tf_train_transform(img, rng, cfg["image_size"],
                                     cfg["resize"])
        else:
            x = T.tf_eval_transform(img, cfg["image_size"], cfg["resize"])
        return x, cfg["labels"][i]
    if cfg.get("device_normalize"):
        # uint8 host path: decode+rescale+crop only; jitter+normalize run
        # inside the jitted step (ops/preprocess.py) — 4× smaller H2D
        if cfg["train"]:
            rng = np.random.default_rng(seed)
            return T.train_transform_u8(img, rng, cfg["image_size"],
                                        cfg["resize"]), cfg["labels"][i]
        return T.eval_transform_u8(img, cfg["image_size"],
                                   cfg["resize"]), cfg["labels"][i]
    if cfg["train"]:
        rng = np.random.default_rng(seed)
        x = T.train_transform(img, rng, cfg["image_size"], cfg["resize"])
    else:
        x = T.eval_transform(img, cfg["image_size"], cfg["resize"])
    return x.astype(np.float32), cfg["labels"][i]


def _worker_load(args) -> tuple[np.ndarray, np.int32]:
    i, seed = args
    return _load_one(_WORKER, i, seed)


class ImageNetLoader:
    """Sharded, multiprocess, epoch-reshuffled batch iterator.

    Yields {"image": (B,H,W,3), "label": (B,) i32} host batches — uint8
    images with ``device_normalize`` (the 1-byte/pixel train wire; the
    jitter/normalize runs as the jitted step's traced prologue), float32
    otherwise.  Compose with ``data.pipeline.DevicePrefetcher`` for
    staged H2D.
    """

    def __init__(self, root_dir: str | None, labels_file: str | None,
                 batch_size: int,
                 train: bool = True, image_size: int = 224, resize: int = 256,
                 num_workers: int = 16, seed: int = 0,
                 process_index: int | None = None,
                 process_count: int | None = None,
                 prefetch_batches: int = 2,
                 device_normalize: bool = False,
                 preprocessing: str = "torch",
                 dataset: ImageNetRecords | None = None):
        import jax

        if preprocessing not in ("torch", "tf"):
            raise ValueError(f"preprocessing must be torch|tf, "
                             f"got {preprocessing!r}")
        if preprocessing == "tf" and device_normalize:
            raise ValueError("tf preprocessing is host-side only "
                             "(mean-centered 0-255 floats); disable "
                             "device_normalize")

        # source: flat folder (default) or dvrec shards (``dataset`` /
        # :meth:`from_records`) — downstream identical, only the worker
        # read path differs
        self.ds = dataset if dataset is not None \
            else ImageNetFolder(root_dir, labels_file)
        pi = jax.process_index() if process_index is None else process_index
        pc = jax.process_count() if process_count is None else process_count
        # per-host shard: every host sees a disjoint 1/pc slice per epoch
        self.host_indices = np.arange(pi, len(self.ds), pc)
        self.batch_size = batch_size
        self.train = train
        self.image_size, self.resize = image_size, resize
        self.num_workers = num_workers
        self.seed = seed
        self.epoch = 0
        self.prefetch_batches = max(1, prefetch_batches)
        self._cfg = dict(labels=self.ds.labels, train=train,
                         image_size=image_size, resize=resize,
                         device_normalize=device_normalize,
                         preprocessing=preprocessing)
        #: what this loader ships per pixel — the input-goodput logs
        #: report H2D traffic against this
        self.wire_dtype = np.uint8 if device_normalize else np.float32
        if isinstance(self.ds, ImageNetRecords):
            self._cfg["entries"] = self.ds.entries
        else:
            self._cfg["root_dir"] = self.ds.root_dir
            self._cfg["files"] = self.ds.files
        self._pool = None
        # create the pool EAGERLY on the main thread. forkserver (spawn as
        # fallback) — NOT fork: by loader-construction time the JAX runtime
        # has live threads, and fork-with-threads can inherit held locks and
        # deadlock nondeterministically on long runs
        if self.num_workers > 0:
            import multiprocessing as mp

            try:
                ctx = mp.get_context("forkserver")
            except ValueError:
                ctx = mp.get_context("spawn")
            self._pool = ctx.Pool(self.num_workers, initializer=_worker_init,
                                  initargs=(self._cfg,))

    @classmethod
    def from_records(cls, root: str, split: str, batch_size: int,
                     **kwargs) -> "ImageNetLoader":
        """Train from ``prepare_data imagenet`` dvrec shards — the
        reference's TFRecord consumption path
        (ResNet/tensorflow/train.py:178-214)."""
        return cls(None, None, batch_size,
                   dataset=ImageNetRecords(root, split), **kwargs)

    def set_epoch(self, epoch: int):
        self.epoch = epoch

    def __len__(self) -> int:
        full = len(self.host_indices) // self.batch_size
        # eval iteration yields one extra weight-padded partial batch so
        # every example is scored exactly once — len() must agree
        if not self.train and len(self.host_indices) % self.batch_size:
            return full + 1
        return full

    def _batch_args(self, idx, seeds, b):
        """(args, n_real) for batch b — padded to the static batch size."""
        from deep_vision_tpu.data.loader import pad_eval_indices

        sel, _, n_real = pad_eval_indices(idx, b * self.batch_size,
                                          self.batch_size)
        args = [(int(i), int(s)) for i, s in
                zip(sel, seeds[b * self.batch_size:
                               b * self.batch_size + self.batch_size])]
        return args, n_real

    def _assemble(self, out, n_real) -> dict:
        batch = {"image": np.stack([o[0] for o in out]),
                 "label": np.asarray([o[1] for o in out], np.int32)}
        if not self.train:
            weight = np.zeros(self.batch_size, np.float32)
            weight[:n_real] = 1.0
            batch["weight"] = weight
        return batch

    def _native_batch(self, args, n_real) -> dict | None:
        """Whole-batch assembly through the C++ reader (data/native):
        positioned reads + crop + flip fused into one call, RNG-exact with
        the Python path (same per-item Generator draw order).  Returns
        None — caller falls back — unless every item is a raw payload at
        the loader's resize on the device-normalize path and the native
        library is available."""
        if not self._cfg.get("device_normalize") or "entries" not in self._cfg:
            return None
        from deep_vision_tpu.data.native import load as load_native

        lib = load_native()
        if lib is None:
            return None
        import ctypes

        entries = self._cfg["entries"]
        size, resize = self.image_size, self.resize
        n = len(args)
        fds = np.empty(n, np.int32)
        offs = np.empty(n, np.int64)
        hs = np.empty(n, np.int32)
        ws = np.empty(n, np.int32)
        tops = np.empty(n, np.int32)
        lefts = np.empty(n, np.int32)
        flips = np.zeros(n, np.uint8)
        labels = np.empty(n, np.int32)
        max_payload = 0
        for j, (i, seed) in enumerate(args):
            path, off, plen, shape = entries[i]
            if shape is None:
                return None  # JPEG payload: decode path handles it
            h, w = int(shape[0]), int(shape[1])
            if min(h, w) != resize or h < size or w < size:
                return None  # stored at a different resize: rescale needed
            if self.train:
                # EXACT draw order of train_transform_u8: flip, then
                # crop top, then crop left, from default_rng(seed)
                r = np.random.default_rng(seed)
                flips[j] = r.random() < 0.5
                tops[j] = r.integers(0, h - size + 1)
                lefts[j] = r.integers(0, w - size + 1)
            else:
                tops[j] = (h - size) // 2
                lefts[j] = (w - size) // 2
            fds[j] = _get_fd(path).fileno()
            offs[j] = off
            hs[j], ws[j] = h, w
            labels[j] = self._cfg["labels"][i]
            max_payload = max(max_payload, plen)
        out = np.empty((n, size, size, 3), np.uint8)
        if getattr(self, "_scratch", None) is None or \
                len(self._scratch) < max_payload:
            self._scratch = np.empty(max_payload, np.uint8)

        def p(a, t):
            return a.ctypes.data_as(ctypes.POINTER(t))

        rc = lib.dvrec_assemble_batch(
            p(fds, ctypes.c_int32), p(offs, ctypes.c_int64),
            p(hs, ctypes.c_int32), p(ws, ctypes.c_int32),
            p(tops, ctypes.c_int32), p(lefts, ctypes.c_int32),
            p(flips, ctypes.c_uint8), n, size,
            p(out, ctypes.c_uint8), p(self._scratch, ctypes.c_uint8))
        if rc != 0:
            return None  # short read etc. — let the Python path report
        batch = {"image": out, "label": labels}
        if not self.train:
            weight = np.zeros(self.batch_size, np.float32)
            weight[:n_real] = 1.0
            batch["weight"] = weight
        return batch

    def __iter__(self) -> Iterator[dict]:
        from collections import deque

        rng = np.random.default_rng((self.seed, self.epoch))
        idx = self.host_indices.copy()
        if self.train:
            rng.shuffle(idx)
        full = len(idx) // self.batch_size
        # eval covers the FULL set: the last partial batch is padded to the
        # static batch size with weight-0 fillers (pad_last semantics)
        partial = (not self.train) and (len(idx) % self.batch_size != 0)
        seeds = rng.integers(0, 2**63 - 1, size=len(idx) + self.batch_size)
        n_batches = full + int(partial)
        if self._pool is None:
            for b in range(n_batches):
                args, n_real = self._batch_args(idx, seeds, b)
                batch = self._native_batch(args, n_real)
                if batch is None:
                    batch = self._assemble(
                        [_load_one(self._cfg, *a) for a in args], n_real)
                yield batch
            return
        # overlapped decode: keep `prefetch_batches` async batches in flight
        # so workers decode batch N+1..N+k while the chip trains on batch N
        # (the DataLoader(num_workers) prefetch role,
        # ResNet/pytorch/train.py:229-234)
        chunk = max(1, self.batch_size // (2 * self.num_workers))
        pending: deque = deque()
        for b in range(n_batches):
            args, n_real = self._batch_args(idx, seeds, b)
            pending.append(
                (self._pool.map_async(_worker_load, args, chunksize=chunk),
                 n_real))
            if len(pending) > self.prefetch_batches:
                res, nr = pending.popleft()
                # a hung decode worker fails the epoch loudly instead of
                # pinning the input pipeline forever
                yield self._assemble(res.get(timeout=600.0), nr)
        while pending:
            res, nr = pending.popleft()
            yield self._assemble(res.get(timeout=600.0), nr)

    def close(self):
        if self._pool is not None:
            self._pool.terminate()  # worker fds die with the processes
            self._pool = None
        _close_fds()  # 0-worker path reads in-process
