"""JPEG record container + decode/augment pipeline (data/jpeg_records.py,
data/augment.py) — the real-ImageNet input path (SURVEY.md §7 hard part
#1; reference analog: per-worker tf.data JPEG decode, SURVEY.md §2a).

Covers: container roundtrip, eval-mode determinism, the train-mode
resume contract (index_offset reproduces the exact augmented stream),
epoch reshuffling, augment-op oracles, the `jpeg:` wiring through
make_dataset, and a host-only decode-throughput probe (slow)."""

import time

import numpy as np
import pytest

from distributed_tensorflow_tpu.data import DataConfig, augment, make_dataset
from distributed_tensorflow_tpu.data.jpeg_records import (
    JpegClassificationDataset, make_jpeg_record_file,
)


def _images(n, h=48, w=40, seed=0):
    rng = np.random.RandomState(seed)
    # smooth gradients survive JPEG quality=90 nearly losslessly
    base = np.linspace(0, 200, h * w * 3).reshape(h, w, 3)
    return np.stack([
        np.clip(base + rng.randint(0, 40), 0, 255).astype(np.uint8)
        for _ in range(n)
    ])


@pytest.fixture()
def jpeg_pair(tmp_path):
    path = str(tmp_path / "train")
    imgs = _images(24)
    labels = np.arange(24) % 7
    n = make_jpeg_record_file(path, imgs, labels)
    assert n == 24
    return path, imgs, labels


def test_eval_batches_deterministic_and_decoded(jpeg_pair):
    path, imgs, labels = jpeg_pair
    ds = JpegClassificationDataset(path, 32, 8, train=False, num_batches=3)
    batches = list(ds)
    assert len(batches) == 3
    b = batches[0]
    assert b["image"].shape == (8, 32, 32, 3)
    assert b["image"].dtype == np.float32
    assert 0.0 <= b["image"].min() and b["image"].max() <= 1.0
    # eval mode: no shuffle — labels stream in file order
    np.testing.assert_array_equal(b["label"], labels[:8])
    # deterministic: decoding the same batch twice is identical
    np.testing.assert_array_equal(ds.batch(1)["image"], ds.batch(1)["image"])
    # decode really round-trips the pixels (quality 90, smooth content)
    dec = augment.resize_center_crop(imgs[0], 32) / 255.0
    np.testing.assert_allclose(b["image"][0], dec, atol=0.05)


def test_train_resume_contract_and_reshuffle(jpeg_pair):
    path, _, _ = jpeg_pair
    ds = JpegClassificationDataset(path, 32, 8, train=True, seed=3)
    # resume contract: a fresh instance at index_offset=k reproduces
    # batch k of the uninterrupted stream — images AND augmentations
    resumed = JpegClassificationDataset(path, 32, 8, train=True, seed=3,
                                        index_offset=2)
    want = ds.batch(2)
    got = resumed.batch(0)
    np.testing.assert_array_equal(want["image"], got["image"])
    np.testing.assert_array_equal(want["label"], got["label"])
    # different global indices give different augmented batches
    assert np.any(ds.batch(0)["image"] != ds.batch(1)["image"])
    # epochs reshuffle: 24 imgs / batch 8 = 3 batches/epoch; epoch 0 vs 1
    # see different label order almost surely
    e0 = np.concatenate([ds.batch(i)["label"] for i in range(3)])
    e1 = np.concatenate([ds.batch(i)["label"] for i in range(3, 6)])
    assert sorted(e0.tolist()) == sorted(e1.tolist())  # same epoch content
    assert np.any(e0 != e1)


def test_make_dataset_jpeg_wiring(jpeg_pair):
    path, _, _ = jpeg_pair
    cfg = DataConfig(dataset=f"jpeg:{path}", global_batch_size=8,
                     image_size=32, num_classes=7)
    it = iter(make_dataset(cfg, num_batches=2))
    b = next(it)
    assert b["image"].shape == (8, 32, 32, 3)
    assert set(np.unique(b["label"])) <= set(range(7))


def test_augment_ops_oracles():
    rng = np.random.RandomState(0)
    img = _images(1, h=60, w=80)[0]
    # random_resized_crop: exact output shape, uint8, content from source
    out = augment.random_resized_crop(img, rng, 32)
    assert out.shape == (32, 32, 3) and out.dtype == np.uint8
    # resize_center_crop: shape + the 0.875 short-side recipe
    out = augment.resize_center_crop(img, 32)
    assert out.shape == (32, 32, 3)
    # hflip: flips exactly half the time, exact mirror when it does
    flipped = augment.hflip(img, np.random.RandomState(1))
    either = (np.array_equal(flipped, img)
              or np.array_equal(flipped, img[:, ::-1]))
    assert either
    # random_crop_flip (CIFAR batch recipe) matches a per-image oracle
    batch = _images(6, h=32, w=32, seed=2).astype(np.float32)
    rng1, rng2 = np.random.RandomState(5), np.random.RandomState(5)
    got = augment.random_crop_flip(batch, rng1, padding=4)
    ys = rng2.randint(0, 9, 6)
    xs = rng2.randint(0, 9, 6)
    padded = np.pad(batch, ((0, 0), (4, 4), (4, 4), (0, 0)))
    want = np.stack([
        padded[i, ys[i]:ys[i] + 32, xs[i]:xs[i] + 32] for i in range(6)
    ])
    flips = rng2.rand(6) < 0.5
    want[flips] = want[flips, :, ::-1]
    np.testing.assert_array_equal(got, want)


@pytest.mark.slow
def test_decode_throughput_host_only(tmp_path):
    """The threaded decode+augment
    path must sustain a real per-core rate (measured ~500 img/s/core at
    256->224 on this container's single core — a 16-core TPU-VM host
    extrapolates to ~8k img/s, past the ~2.5k img/s bench step rate).
    Thread-pool scaling is asserted only where the host has cores to
    scale onto; PIL releases the GIL during decode."""
    import os

    path = str(tmp_path / "tp")
    n = 128
    imgs = _images(n, h=256, w=256, seed=1)
    make_jpeg_record_file(path, imgs, np.zeros(n, np.int64))

    def rate_of(decoder):
        try:
            ds = JpegClassificationDataset(path, 224, 64, train=True,
                                           decoder=decoder)
        except RuntimeError:  # native lib unavailable on this host
            return None
        ds.batch(0)  # warm the pool + caches
        t0 = time.perf_counter()
        for i in range(1, 5):
            ds.batch(i)
        return 4 * 64 / (time.perf_counter() - t0)

    rate = rate_of("pil")
    native_rate = rate_of("native")
    print(f"decode+augment: pil {rate:.0f} img/s, native "
          f"{native_rate and round(native_rate)} img/s "
          f"({os.cpu_count()} cores)")
    # well under the idle single-core measurement (~500 img/s) — the CI
    # box may be sharing its core with concurrent jobs
    assert rate > 60, rate
    if native_rate is not None:
        # the C++ stage must not be slower than PIL (measured ~1.9x)
        assert native_rate > rate * 0.9, (native_rate, rate)
    if (os.cpu_count() or 1) >= 4:
        ds1 = JpegClassificationDataset(path, 224, 64, train=True,
                                        n_threads=1, decoder="pil")
        ds1.batch(0)
        t0 = time.perf_counter()
        ds1.batch(1)
        serial = 64 / (time.perf_counter() - t0)
        print(f"single-thread: {serial:.0f} images/sec")
        assert rate > 2 * serial, (rate, serial)


def test_imagefolder_converter_roundtrip(tmp_path):
    """tools/make_jpeg_records.py: ImageFolder tree -> record pair by raw
    byte copy (lossless — decoded pixels identical to the source files),
    labels from sorted class dirs, readable by JpegClassificationDataset."""
    import io
    import json
    import sys

    from PIL import Image

    sys.path.insert(0, str(__import__("pathlib").Path(__file__).parents[1]))
    from tools.make_jpeg_records import convert

    src = tmp_path / "imagefolder"
    imgs = _images(6, h=40, w=40)
    for i, cls in enumerate(["cat", "dog", "ant"] * 2):
        d = src / cls
        d.mkdir(exist_ok=True, parents=True)
        Image.fromarray(imgs[i]).save(d / f"img{i}.jpg", "JPEG", quality=92)

    out = str(tmp_path / "rec")
    n = convert(str(src), out, shuffle_seed=None)
    assert n == 6
    classes = json.load(open(out + ".classes.json"))
    assert classes == ["ant", "cat", "dog"]

    ds = JpegClassificationDataset(out, 32, 6, train=False, num_batches=1)
    b = next(iter(ds))
    assert b["image"].shape == (6, 32, 32, 3)
    # labels follow sorted-class convention: ant=0, cat=1, dog=2
    assert sorted(b["label"].tolist()) == [0, 0, 1, 1, 2, 2]
    # raw-copy losslessness AND offset/label correspondence: with no
    # shuffle the stream is label-major, so entry i's bytes must equal
    # the i-th file of the sorted walk of its OWN class
    per_class_files = {
        c: sorted(p for p in (src / c).rglob("*") if p.suffix == ".jpg")
        for c in classes
    }
    cursor = {c: 0 for c in classes}
    for i in range(6):
        entry = ds.entries[i]
        raw = bytes(
            ds._data[entry["offset"]: entry["offset"] + entry["length"]]
        )
        cls = classes[int(entry["label"])]
        expect = per_class_files[cls][cursor[cls]]
        cursor[cls] += 1
        assert raw == expect.read_bytes(), (i, cls, expect)


def test_converter_limit_without_shuffle_keeps_all_classes(tmp_path):
    """--limit + --no-shuffle must not truncate the label-major list to
    the first class(es): the subset is interleaved round-robin so every
    class stays represented."""
    import json
    import sys

    import numpy as np
    from PIL import Image

    sys.path.insert(0, str(__import__("pathlib").Path(__file__).parents[1]))
    from tools.make_jpeg_records import convert
    from distributed_tensorflow_tpu.data.jpeg_records import _ENTRY

    src = tmp_path / "imagefolder"
    imgs = _images(9, h=24, w=24)
    for i in range(9):
        d = src / f"class{i // 3}"  # 3 classes x 3 images
        d.mkdir(exist_ok=True, parents=True)
        Image.fromarray(imgs[i]).save(d / f"img{i}.jpg", "JPEG")

    out = str(tmp_path / "rec")
    n = convert(str(src), out, shuffle_seed=None, limit=3)
    assert n == 3
    entries = np.fromfile(out + ".idx", _ENTRY)
    assert sorted(entries["label"].tolist()) == [0, 1, 2]
    assert len(json.load(open(out + ".classes.json"))) == 3


def test_native_decoder_matches_pil_policy(tmp_path):
    """Native (C++/libjpeg) and PIL decoders draw IDENTICAL crop/flip
    decisions (augment.sample_crop_rect is the single policy definition)
    and resample within a small tolerance; both are deterministic."""
    from distributed_tensorflow_tpu.data import native_jpeg

    if not native_jpeg.available():
        pytest.skip("native jpeg library unavailable (no g++/libjpeg)")

    path = str(tmp_path / "rec")
    imgs = _images(16, h=64, w=56)
    make_jpeg_record_file(path, imgs, np.arange(16) % 4)

    for train in (False, True):
        dn = JpegClassificationDataset(path, 32, 8, train=train,
                                       decoder="native")
        dp = JpegClassificationDataset(path, 32, 8, train=train,
                                       decoder="pil")
        bn, bp = dn.batch(0), dp.batch(0)
        np.testing.assert_array_equal(bn["label"], bp["label"])
        # same crops/flips, different resampling filter: close, not equal
        assert np.abs(bn["image"] - bp["image"]).max() < 0.08, train
        np.testing.assert_array_equal(
            dn.batch(1)["image"], dn.batch(1)["image"])

    with pytest.raises(ValueError, match="decoder"):
        JpegClassificationDataset(path, 32, 8, decoder="webp")


def test_native_decoder_zero_fills_corrupt_stream(tmp_path):
    from distributed_tensorflow_tpu.data import native_jpeg

    if not native_jpeg.available():
        pytest.skip("native jpeg library unavailable")

    path = str(tmp_path / "rec")
    imgs = _images(8, h=40, w=40)
    make_jpeg_record_file(path, imgs, np.arange(8))
    # truncate record 3's stream in the index (simulates corruption)
    from distributed_tensorflow_tpu.data.jpeg_records import _ENTRY

    entries = np.fromfile(path + ".idx", _ENTRY)
    entries[3]["length"] = 10
    entries.tofile(path + ".idx")
    ds = JpegClassificationDataset(path, 32, 8, train=False,
                                   decoder="native")
    b = ds.batch(0)
    assert b["image"][3].max() == 0.0  # zero-filled, not crashed
    assert b["image"][0].max() > 0.0


def test_jpeg_per_host_sharding_disjoint(tmp_path, monkeypatch):
    """Multi-host contract: each process decodes a DISJOINT strided slice
    of the epoch order and the union covers the epoch exactly —
    simulated by pinning _shard/_n_shards (the tf.data.shard analog)."""
    path = str(tmp_path / "rec")
    imgs = _images(24, h=32, w=32)
    make_jpeg_record_file(path, imgs, np.arange(24))

    seen = []
    for shard in range(2):
        ds = JpegClassificationDataset(path, 32, 8, train=True, seed=1)
        ds._shard, ds._n_shards = shard, 2
        # local batch must be global/2 per host; recompute as the
        # constructor would under process_count=2
        ds.local_bs = 4
        labels = np.concatenate(
            [ds.batch(i)["label"] for i in range(ds._batches_per_epoch())]
        )
        seen.append(labels)
    a, b = seen
    assert len(set(a.tolist()) & set(b.tolist())) == 0  # disjoint
    assert sorted(set(a.tolist()) | set(b.tolist())) == sorted(
        np.arange(24).tolist())  # epoch covered


def test_native_decoder_grayscale_source(tmp_path):
    """Grayscale JPEGs (1-channel sources exist in real ImageNet) must
    decode to RGB in both tiers — libjpeg's out_color_space=JCS_RGB
    upsamples gray, PIL's convert('RGB') likewise."""
    import io

    from PIL import Image

    from distributed_tensorflow_tpu.data import native_jpeg
    from distributed_tensorflow_tpu.data.jpeg_records import _ENTRY

    if not native_jpeg.available():
        pytest.skip("native jpeg library unavailable")

    path = str(tmp_path / "rec")
    gray = _images(4, h=40, w=40)[..., 0]  # [N, H, W] single channel
    entries = np.empty(4, _ENTRY)
    with open(path + ".dat", "wb") as f:
        off = 0
        for i in range(4):
            buf = io.BytesIO()
            Image.fromarray(gray[i], "L").save(buf, "JPEG", quality=92)
            raw = buf.getvalue()
            f.write(raw)
            entries[i] = (off, len(raw), i)
            off += len(raw)
    entries.tofile(path + ".idx")

    bn = JpegClassificationDataset(path, 32, 4, train=False,
                                   decoder="native").batch(0)
    bp = JpegClassificationDataset(path, 32, 4, train=False,
                                   decoder="pil").batch(0)
    assert bn["image"].shape == (4, 32, 32, 3)
    # gray upsampled: all three channels equal
    np.testing.assert_array_equal(bn["image"][..., 0], bn["image"][..., 1])
    assert np.abs(bn["image"] - bp["image"]).max() < 0.08
