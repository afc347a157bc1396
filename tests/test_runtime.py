"""Native runtime (C++ loader + checksummed IO): build, native↔fallback
parity, shard disjointness, resume, corruption detection."""

import ctypes
import os
import random

import numpy as np
import pytest

from distributed_tensorflow_tpu.runtime import (
    RecordFileLoader, available, epoch_permutation, load_library,
    read_payload, write_payload,
)
from distributed_tensorflow_tpu.runtime import io as io_lib
from distributed_tensorflow_tpu.runtime import native


@pytest.fixture(scope="module")
def lib():
    lib = load_library()
    if lib is None:
        pytest.skip("native toolchain unavailable")
    return lib


@pytest.fixture()
def record_file(tmp_path):
    rng = np.random.RandomState(0)
    n, rec = 64, 20
    data = rng.randint(0, 256, (n, rec)).astype(np.uint8)
    path = str(tmp_path / "data.bin")
    data.tofile(path)
    return path, data


def test_native_builds(lib):
    assert available()


def test_library_is_keyed_by_its_source(tmp_path):
    """After a copy or a checkout mtimes say nothing, so the built library
    is named by a hash of its source: another source, another path — a
    stale binary is never loaded."""
    src = tmp_path / "k.cpp"
    src.write_text('extern "C" int k() { return 1; }\n')
    first = native.library_path(str(src), "k")
    assert first == native.library_path(str(src), "k")
    assert os.path.basename(first).startswith("libk-")
    src.write_text('extern "C" int k() { return 2; }\n')
    assert native.library_path(str(src), "k") != first


def test_permutation_parity(lib):
    for n, seed in [(1, 0), (17, 3), (256, 12345)]:
        out = (ctypes.c_int64 * n)()
        lib.dtf_epoch_permutation(n, seed, out)
        np.testing.assert_array_equal(
            np.asarray(out), epoch_permutation(n, seed)
        )
        assert sorted(out) == list(range(n))


def test_native_matches_fallback(record_file, lib):
    path, _ = record_file
    kw = dict(seed=7, n_shards=2, shard=1, num_batches=10)
    nat = list(RecordFileLoader(path, 20, 8, use_native=True, **kw))
    py = list(RecordFileLoader(path, 20, 8, use_native=False, **kw))
    assert len(nat) == len(py) == 10
    for a, b in zip(nat, py):
        np.testing.assert_array_equal(a, b)


def test_batches_match_oracle(record_file, lib):
    path, data = record_file
    ldr = RecordFileLoader(path, 20, 8, seed=3, num_batches=6,
                           use_native=True)
    oracle = RecordFileLoader(path, 20, 8, seed=3, use_native=False)
    for bi, batch in enumerate(ldr):
        np.testing.assert_array_equal(batch, data[oracle.batch_indices(bi)])


def test_shards_disjoint_and_cover_epoch(record_file):
    path, _ = record_file
    seen = []
    for shard in range(2):
        ldr = RecordFileLoader(path, 20, 8, seed=1, shard=shard, n_shards=2,
                               use_native=False)
        for bi in range(ldr.batches_per_epoch):
            seen.append(ldr.batch_indices(bi))
    flat = np.concatenate(seen)
    # one epoch over both shards touches every record exactly once
    assert sorted(flat.tolist()) == list(range(64))


def test_resume_continues_stream(record_file, lib):
    path, _ = record_file
    full = list(RecordFileLoader(path, 20, 8, seed=2, num_batches=8,
                                 use_native=True))
    resumed = list(RecordFileLoader(path, 20, 8, seed=2, num_batches=5,
                                    start_batch=3, use_native=True))
    for a, b in zip(full[3:], resumed):
        np.testing.assert_array_equal(a, b)


def test_next_without_release_does_not_deadlock(record_file, lib):
    """Holding several batches before releasing any must not starve the
    producers (next() must wake a worker when it lowers in-flight)."""
    import threading

    path, _ = record_file
    h = lib.dtf_loader_create(path.encode(), 20, 8, 2, 2, 0, 0, 1, 0)
    assert h
    held = []

    def consume():
        for _ in range(3):  # depth=2: the 3rd next needs a producer wakeup
            held.append(lib.dtf_loader_next(h))

    t = threading.Thread(target=consume, daemon=True)
    t.start()
    t.join(timeout=30)
    ok = not t.is_alive()
    if ok:
        for b in held:
            lib.dtf_loader_release(h, b)
        lib.dtf_loader_destroy(h)  # leak on failure: destroy would race
    assert ok, "loader deadlocked when batches were held across next() calls"


def test_decode_hook(record_file):
    path, data = record_file
    ldr = RecordFileLoader(
        path, 20, 8, num_batches=2,
        decode=lambda raw: {"sum": raw.sum(axis=1)},
    )
    out = list(ldr)
    assert set(out[0]) == {"sum"} and out[0]["sum"].shape == (8,)


def test_io_roundtrip(tmp_path):
    path = str(tmp_path / "shard-0")
    payload = random.Random(7).randbytes(10_000)  # seeded: reproducible
    write_payload(path, payload)
    assert read_payload(path) == payload
    # overwrite is atomic: old file stays valid if we re-write
    write_payload(path, b"second")
    assert read_payload(path) == b"second"
    assert not os.path.exists(path + ".tmp")


def test_io_detects_corruption(tmp_path):
    path = str(tmp_path / "shard-1")
    write_payload(path, b"x" * 1000)
    raw = bytearray(open(path, "rb").read())
    raw[500] ^= 0xFF
    open(path, "wb").write(bytes(raw))
    with pytest.raises(OSError, match="CRC"):
        read_payload(path)


def test_io_python_fallback_format_compatible(tmp_path, monkeypatch):
    """Bytes written natively must read through the Python fallback and
    vice versa (same trailer format)."""
    p1, p2 = str(tmp_path / "a"), str(tmp_path / "b")
    write_payload(p1, b"native-written")  # native (if available)
    monkeypatch.setattr(io_lib.native, "load_library", lambda: None)
    assert read_payload(p1) == b"native-written"
    write_payload(p2, b"python-written")
    monkeypatch.undo()
    assert read_payload(p2) == b"python-written"


def test_record_classification_dataset(tmp_path):
    from distributed_tensorflow_tpu.data.records import (
        RecordClassificationDataset, make_record_file,
    )

    rng = np.random.RandomState(0)
    images = rng.randint(0, 256, (32, 4, 4, 1)).astype(np.uint8)
    labels = rng.randint(0, 10, 32).astype(np.int32)
    path = str(tmp_path / "imgs.bin")
    rb = make_record_file(path, images, labels)
    assert rb == 4 * 4 * 1 + 4
    ds = RecordClassificationDataset(path, (4, 4, 1), 8, num_batches=4)
    batches = list(ds)
    assert len(batches) == 4
    b = batches[0]
    assert b["image"].shape == (8, 4, 4, 1) and b["image"].dtype == np.float32
    assert b["label"].shape == (8,) and b["label"].dtype == np.int32
    assert 0.0 <= b["image"].min() and b["image"].max() <= 1.0
    # labels travel with their images through the shuffle
    ds2 = RecordClassificationDataset(path, (4, 4, 1), 8, num_batches=1,
                                      use_native=False)
    b2 = next(iter(ds2))
    np.testing.assert_array_equal(b["label"], b2["label"])
    np.testing.assert_allclose(b["image"], b2["image"])
