"""ImageNet pipeline tests on synthetic JPEGs (no dataset download)."""

import os

import numpy as np
import pytest

from deep_vision_tpu.data import transforms as T
from deep_vision_tpu.data.imagenet import ImageNetFolder, ImageNetLoader

PIL = pytest.importorskip("PIL")
from PIL import Image  # noqa: E402


@pytest.fixture(scope="module")
def fake_imagenet(tmp_path_factory):
    root = tmp_path_factory.mktemp("imagenet")
    img_dir = root / "train"
    img_dir.mkdir()
    synsets = ["n01440764", "n01443537", "n01484850"]
    rng = np.random.default_rng(0)
    for s_i, syn in enumerate(synsets):
        for j in range(6):
            arr = rng.integers(0, 255, size=(40 + 8 * s_i, 64, 3),
                               dtype=np.uint8)
            Image.fromarray(arr).save(img_dir / f"{syn}_{j}.JPEG")
    labels_file = root / "metadata.txt"
    labels_file.write_text(
        "\n".join(f"{s} class_{i}" for i, s in enumerate(synsets)))
    return str(img_dir), str(labels_file)


def test_folder_labels_from_filename_prefix(fake_imagenet):
    root, labels = fake_imagenet
    ds = ImageNetFolder(root, labels)
    assert len(ds) == 18
    img, label = ds.read(0)
    assert img.ndim == 3 and img.shape[2] == 3
    assert 0 <= label < 3


def test_transforms_shapes_and_ranges():
    rng = np.random.default_rng(0)
    img = rng.integers(0, 255, size=(300, 400, 3), dtype=np.uint8)
    out = T.train_transform(img, rng, size=224, resize=256)
    assert out.shape == (224, 224, 3) and out.dtype == np.float32
    ev = T.eval_transform(img, size=224, resize=256)
    assert ev.shape == (224, 224, 3)
    # rescale puts the SHORTER side at the target
    r = T.rescale(img, 256)
    assert min(r.shape[:2]) == 256 and max(r.shape[:2]) == 341


def test_rescale_no_op_and_portrait():
    img = np.zeros((500, 250, 3), np.uint8)
    r = T.rescale(img, 100)
    assert r.shape == (200, 100, 3)


def test_center_crop_is_deterministic():
    img = np.arange(10 * 10 * 3, dtype=np.uint8).reshape(10, 10, 3)
    a = T.center_crop(img, 4)
    b = T.center_crop(img, 4)
    np.testing.assert_array_equal(a, b)
    assert a.shape == (4, 4, 3)


def test_loader_batches_and_reshuffle(fake_imagenet):
    root, labels = fake_imagenet
    loader = ImageNetLoader(root, labels, batch_size=4, train=True,
                            image_size=32, resize=36, num_workers=0,
                            process_index=0, process_count=1)
    batches = list(loader)
    assert len(batches) == 4  # 18 // 4
    b = batches[0]
    assert b["image"].shape == (4, 32, 32, 3)
    assert b["label"].dtype == np.int32
    loader.set_epoch(1)
    batches2 = list(loader)
    # different epoch ⇒ different order (labels differ somewhere)
    l1 = np.concatenate([b["label"] for b in batches])
    l2 = np.concatenate([b["label"] for b in batches2])
    assert not np.array_equal(l1, l2)


def test_loader_host_sharding(fake_imagenet):
    root, labels = fake_imagenet
    l0 = ImageNetLoader(root, labels, batch_size=2, train=False,
                        image_size=32, resize=36, num_workers=0,
                        process_index=0, process_count=2)
    l1 = ImageNetLoader(root, labels, batch_size=2, train=False,
                        image_size=32, resize=36, num_workers=0,
                        process_index=1, process_count=2)
    assert len(set(l0.host_indices) & set(l1.host_indices)) == 0
    assert len(l0.host_indices) + len(l1.host_indices) == 18


def test_multiprocess_workers(fake_imagenet):
    root, labels = fake_imagenet
    loader = ImageNetLoader(root, labels, batch_size=4, train=True,
                            image_size=32, resize=36, num_workers=2,
                            process_index=0, process_count=1)
    try:
        b = next(iter(loader))
        assert b["image"].shape == (4, 32, 32, 3)
        assert np.isfinite(b["image"]).all()
    finally:
        loader.close()


def test_device_normalize_path_matches_host(fake_imagenet):
    """uint8 loader + device jitter_normalize(train=False) must reproduce
    the host eval_transform exactly (same crop, same normalization)."""
    import jax
    import jax.numpy as jnp

    from deep_vision_tpu.ops.preprocess import jitter_normalize

    root, labels = fake_imagenet
    host = ImageNetLoader(root, labels, batch_size=4, train=False,
                          image_size=32, resize=36, num_workers=0,
                          process_index=0, process_count=1)
    dev = ImageNetLoader(root, labels, batch_size=4, train=False,
                         image_size=32, resize=36, num_workers=0,
                         process_index=0, process_count=1,
                         device_normalize=True)
    hb = next(iter(host))
    db = next(iter(dev))
    assert db["image"].dtype == np.uint8
    out = np.asarray(jitter_normalize(jnp.asarray(db["image"]),
                                      jax.random.PRNGKey(0), train=False))
    np.testing.assert_allclose(out, hb["image"], atol=1e-5)


@pytest.mark.slow
def test_device_preprocess_trains(fake_imagenet, tmp_path, mesh1):
    """End-to-end: uint8 batches through Trainer(preprocess_fn=...) —
    the fused-device path the ImageNet CLI uses by default."""
    from deep_vision_tpu.core.config import get_config
    from deep_vision_tpu.core.trainer import Trainer
    from deep_vision_tpu.ops.preprocess import make_imagenet_preprocess
    from deep_vision_tpu.tasks.classification import ClassificationTask

    root, labels = fake_imagenet
    cfg = get_config("resnet50")
    cfg.total_epochs = 1
    cfg.batch_size = cfg.eval_batch_size = 4
    cfg.image_size = 32
    loader = ImageNetLoader(root, labels, batch_size=4, train=True,
                            image_size=32, resize=36, num_workers=0,
                            process_index=0, process_count=1,
                            device_normalize=True)
    trainer = Trainer(cfg, cfg.model(), ClassificationTask(cfg.num_classes),
                      mesh=mesh1, workdir=str(tmp_path),
                      preprocess_fn=make_imagenet_preprocess())
    state = trainer.fit(loader, None)
    assert int(np.asarray(state.step)) == len(loader)


def test_val_loader_isolated_from_train_with_zero_workers(fake_imagenet):
    """Regression: two 0-worker loaders must not share decode state —
    val must read val files with eval transforms."""
    root, labels = fake_imagenet
    tr = ImageNetLoader(root, labels, batch_size=4, train=True,
                        image_size=32, resize=36, num_workers=0,
                        process_index=0, process_count=1)
    va = ImageNetLoader(root, labels, batch_size=4, train=False,
                        image_size=32, resize=36, num_workers=0,
                        process_index=0, process_count=1)
    _ = next(iter(tr))  # train first, as fit() does
    b1 = next(iter(va))
    b2 = next(iter(va))
    # eval transform is deterministic ⇒ identical batches across epochs
    np.testing.assert_array_equal(b1["image"], b2["image"])
    np.testing.assert_array_equal(b1["label"], b2["label"])


def test_eval_pads_final_partial_batch(fake_imagenet):
    root, labels = fake_imagenet
    va = ImageNetLoader(root, labels, batch_size=4, train=False,
                        image_size=32, resize=36, num_workers=0,
                        process_index=0, process_count=1)
    batches = list(va)
    assert len(batches) == 5  # 18 imgs → 4 full + 1 padded
    w = np.concatenate([b["weight"] for b in batches])
    assert w.sum() == 18.0  # every real image counted exactly once
    assert batches[-1]["image"].shape == (4, 32, 32, 3)  # static shape


def test_prefetch_propagates_producer_errors():
    import jax
    import pytest as _pytest

    from deep_vision_tpu.data.pipeline import DevicePrefetcher
    from deep_vision_tpu.parallel import make_mesh

    mesh = make_mesh({"data": 1}, devices=jax.devices()[:1])

    def bad_iter():
        yield {"image": np.zeros((2, 4, 4, 1), np.float32)}
        raise RuntimeError("decode failed")

    pf = DevicePrefetcher(mesh)
    try:
        it = pf.iterate(bad_iter())
        next(it)
        with _pytest.raises(RuntimeError, match="decode failed"):
            next(it)
    finally:
        pf.close()


def test_synthetic_imagenet_tree_is_read_by_the_loader(tmp_path):
    """``data/synthetic.make_synthetic_imagenet`` (chip_smoke.py packs its
    records from it) writes a tree ``ImageNetLoader`` reads: every image
    counted once, labels among the 8 synsets, the asked size."""
    from deep_vision_tpu.data.synthetic import make_synthetic_imagenet

    root, labels, val_root = make_synthetic_imagenet(
        str(tmp_path), n_images=20, jpeg_size=40, val_images=6)
    assert len(os.listdir(root)) == 20 and len(os.listdir(val_root)) == 6
    for split, count in ((root, 20), (val_root, 6)):
        loader = ImageNetLoader(split, labels, batch_size=4, train=False,
                                image_size=32, resize=36, num_workers=0,
                                process_index=0, process_count=1)
        batches = list(loader)
        weight = np.concatenate([b["weight"] for b in batches])
        label = np.concatenate([b["label"] for b in batches])
        assert weight.sum() == count
        assert all(b["image"].shape == (4, 32, 32, 3) for b in batches)
        # file i carries synset i % 8, so the first `count` labels cycle
        assert sorted(label[weight > 0]) == sorted(
            i % 8 for i in range(count))


def test_tf_preprocessing_semantics():
    """TF 'ResNet preprocessing' variant (ResNet/tensorflow/data_load.py):
    aspect-preserving resize, central crop, and mean subtraction in RAW
    0-255 space with NO std scaling."""
    rng = np.random.default_rng(3)
    img = rng.integers(0, 255, size=(100, 200, 3), dtype=np.uint8)
    out = T.tf_eval_transform(img, size=64, resize=96)
    assert out.shape == (64, 64, 3) and out.dtype == np.float32
    # exact mean subtraction: central crop of the resized image minus means
    resized = T.rescale(img, 96)
    assert resized.shape[0] == 96  # smaller side pinned, aspect kept
    assert resized.shape[1] == 192
    expect = T.center_crop(resized, 64).astype(np.float32) - T.TF_CHANNEL_MEANS
    np.testing.assert_allclose(out, expect, atol=1e-5)
    # train path: right shape/range, varies with rng
    a = T.tf_train_transform(img, np.random.default_rng(0), 64, 96)
    b = T.tf_train_transform(img, np.random.default_rng(7), 64, 96)
    assert a.shape == (64, 64, 3)
    assert a.min() >= -T.TF_CHANNEL_MEANS.max() - 1e-3
    assert a.max() <= 255.0
    assert not np.allclose(a, b)


def test_loader_tf_preprocessing(fake_imagenet):
    root, labels = fake_imagenet
    loader = ImageNetLoader(root, labels, batch_size=4, train=False,
                            image_size=32, resize=40, num_workers=0,
                            process_index=0, process_count=1,
                            preprocessing="tf")
    batch = next(iter(loader))
    x = batch["image"]
    assert x.shape == (4, 32, 32, 3) and x.dtype == np.float32
    # mean-centered raw-range values, NOT [0,1]-normalized
    assert x.min() < -50 and x.max() > 50
    with pytest.raises(ValueError, match="host-side only"):
        ImageNetLoader(root, labels, 4, num_workers=0, process_index=0,
                       process_count=1, preprocessing="tf",
                       device_normalize=True)


def test_record_loader_matches_folder(fake_imagenet, tmp_path):
    """The dvrec consumption path (reference TFRecord trainer role,
    ResNet/tensorflow/train.py:178-214): shards built by prepare_imagenet
    feed the same loader and yield byte-identical eval batches to the
    folder path."""
    from deep_vision_tpu.data import prep

    root, labels = fake_imagenet
    out = str(tmp_path / "recs")
    n = prep.prepare_imagenet(root, labels, out, "val", num_shards=3,
                              num_workers=1)
    assert n == 18
    kwargs = dict(train=False, image_size=32, resize=40, num_workers=0,
                  process_index=0, process_count=1)
    folder = ImageNetLoader(root, labels, batch_size=6, **kwargs)
    records = ImageNetLoader.from_records(out, "val", batch_size=6, **kwargs)
    assert len(records) == len(folder)
    # deterministic eval transform + same source images → same multiset of
    # (label, image-checksum) pairs across the epoch
    def sig(loader):
        out = []
        for b in loader:
            for img, lab in zip(b["image"], b["label"]):
                out.append((int(lab), float(np.abs(img).sum())))
        return sorted(out)
    np.testing.assert_allclose(np.asarray(sig(records)),
                               np.asarray(sig(folder)), rtol=1e-6)


def test_raw_record_loader_matches_folder(fake_imagenet, tmp_path):
    """`--store raw` shards (decode ONCE at build, store rescaled uint8 —
    the decode-free read path that feeds a chip from one host core) must
    yield the SAME eval batches as the decode-at-read folder path: both
    rescale the same decoded pixels with the same backend, just at
    different times."""
    from deep_vision_tpu.data import prep

    root, labels = fake_imagenet
    out = str(tmp_path / "recs_raw")
    n = prep.prepare_imagenet(root, labels, out, "val", num_shards=3,
                              num_workers=1, store="raw", resize=40)
    assert n == 18
    kwargs = dict(train=False, image_size=32, resize=40, num_workers=0,
                  process_index=0, process_count=1)
    folder = ImageNetLoader(root, labels, batch_size=6, **kwargs)
    raw = ImageNetLoader.from_records(out, "val", batch_size=6, **kwargs)
    assert len(raw) == len(folder)
    # shard fan-out interleaves items, so compare the epoch as a multiset
    # of (label, image-checksum) pairs — deterministic eval transform +
    # same decoded pixels ⇒ identical signatures
    def sig(loader):
        res = []
        for b in loader:
            for img, lab in zip(b["image"], b["label"]):
                res.append((int(lab), float(np.abs(img).sum())))
        return sorted(res)
    np.testing.assert_allclose(np.asarray(sig(raw)),
                               np.asarray(sig(folder)), rtol=1e-6)


def test_raw_record_loader_train_and_eval_len(fake_imagenet, tmp_path):
    from deep_vision_tpu.data import prep

    root, labels = fake_imagenet
    out = str(tmp_path / "recs_raw")
    prep.prepare_imagenet(root, labels, out, "train", num_shards=2,
                          num_workers=1, store="raw", resize=40)
    loader = ImageNetLoader.from_records(
        out, "train", batch_size=4, train=True, image_size=32, resize=40,
        num_workers=0, process_index=0, process_count=1,
        device_normalize=True)
    batches = list(loader)
    assert len(batches) == 18 // 4
    assert batches[0]["image"].shape == (4, 32, 32, 3)
    assert batches[0]["image"].dtype == np.uint8
    # eval: len() must count the padded partial batch it yields (ADVICE r2)
    ev = ImageNetLoader.from_records(
        out, "train", batch_size=4, train=False, image_size=32, resize=40,
        num_workers=0, process_index=0, process_count=1)
    assert len(ev) == len(list(ev)) == 5  # 18 → 4 full + 1 padded


def test_native_reader_matches_python_path(fake_imagenet, tmp_path,
                                           monkeypatch):
    """The C++ batch assembler (data/native/dvrec_reader.cc) must be
    BIT-EXACT with the Python read path — same per-item RNG draw order
    (flip, crop top, crop left), same crops, train and eval — so turning
    it on cannot change a training trajectory."""
    from deep_vision_tpu.data import native, prep

    if native.load() is None:
        pytest.skip("no C++ toolchain")
    root, labels = fake_imagenet
    out = str(tmp_path / "recs_raw")
    prep.prepare_imagenet(root, labels, out, "train", num_shards=2,
                          num_workers=1, store="raw", resize=40)

    def batches(train):
        loader = ImageNetLoader.from_records(
            out, "train", batch_size=4, train=train, image_size=32,
            resize=40, num_workers=0, process_index=0, process_count=1,
            device_normalize=True, seed=7)
        return list(loader)

    native_train = batches(True)
    native_eval = batches(False)
    assert any(b["image"].flags["C_CONTIGUOUS"] for b in native_train)
    # force the pure-Python path and compare byte-for-byte
    monkeypatch.setattr(
        "deep_vision_tpu.data.imagenet.ImageNetLoader._native_batch",
        lambda self, args, n_real: None)
    py_train = batches(True)
    py_eval = batches(False)
    assert len(native_train) == len(py_train) > 0
    for nb, pb in zip(native_train + native_eval, py_train + py_eval):
        np.testing.assert_array_equal(nb["label"], pb["label"])
        np.testing.assert_array_equal(nb["image"], pb["image"])
        if "weight" in pb:
            np.testing.assert_array_equal(nb["weight"], pb["weight"])


def test_record_loader_multiprocess(fake_imagenet, tmp_path):
    from deep_vision_tpu.data import prep

    root, labels = fake_imagenet
    out = str(tmp_path / "recs")
    prep.prepare_imagenet(root, labels, out, "train", num_shards=2,
                          num_workers=1)
    loader = ImageNetLoader.from_records(out, "train", batch_size=4,
                                         train=True, image_size=32,
                                         resize=40, num_workers=2,
                                         process_index=0, process_count=1)
    try:
        batches = list(loader)
        assert len(batches) == 18 // 4
        assert batches[0]["image"].shape == (4, 32, 32, 3)
        assert all(0 <= l < 3 for b in batches for l in b["label"])
    finally:
        loader.close()


def test_resize_backends_preserve_dtype():
    """resize_bilinear keeps dtype on BOTH backends; the PIL fallback must
    not truncate float images to uint8 (per-channel mode-F path)."""
    import deep_vision_tpu.data.transforms as T

    img_u8 = np.random.default_rng(0).integers(
        0, 255, (40, 60, 3), dtype=np.uint8)
    img_f = img_u8.astype(np.float32) / 255.0
    for backend_cv2 in (T._cv2, None):
        saved = T._cv2
        T._cv2 = backend_cv2
        try:
            out_u8 = T.resize_bilinear(img_u8, 30, 20)
            out_f = T.resize_bilinear(img_f, 30, 20)
        finally:
            T._cv2 = saved
        assert out_u8.shape == (20, 30, 3) and out_u8.dtype == np.uint8
        assert out_f.shape == (20, 30, 3) and out_f.dtype == np.float32
        # floats stay in range — a uint8 truncation would zero them out
        assert 0.2 < float(out_f.mean()) < 0.8
