"""Real-file parse paths of the example data loaders.

The zero-egress sandbox means the synthetic fallback branch is the only one
normally executed; these tests fabricate VALID on-disk datasets — CIFAR-10
pickle batches and MNIST IDX(.gz) files — and assert the real parse path
returns them (bit-exact pixels, labels, normalization), with the
`last_load_synthetic` flag cleared. Ref formats:
/root/reference/examples/cnn/data/cifar10.py (pickle batches),
mnist.py (IDX).
"""

import gzip
import importlib
import os
import pickle
import struct
import sys

import numpy as np
import pytest


@pytest.fixture()
def loaders():
    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "examples", "cnn"))
    from data import cifar10, mnist
    importlib.reload(cifar10)
    importlib.reload(mnist)
    yield cifar10, mnist


def _write_cifar_batch(path, n, seed):
    rng = np.random.RandomState(seed)
    data = rng.randint(0, 256, (n, 3072), dtype=np.uint8)
    labels = rng.randint(0, 10, n).tolist()
    with open(path, "wb") as f:
        pickle.dump({b"data": data, b"labels": labels}, f)
    return data, labels


def test_cifar10_real_parse(tmp_path, loaders, monkeypatch):
    cifar10, _ = loaders
    d = tmp_path / "cifar-10-batches-py"
    d.mkdir()
    raw = {}
    for i in range(1, 6):
        raw[i] = _write_cifar_batch(str(d / f"data_batch_{i}"), 20, i)
    test_raw = _write_cifar_batch(str(d / "test_batch"), 12, 99)
    monkeypatch.setattr(cifar10, "SEARCH_DIRS", [str(d)])

    tx, ty, vx, vy = cifar10.load()
    assert cifar10.last_load_synthetic is False
    assert tx.shape == (100, 3, 32, 32) and tx.dtype == np.float32
    assert vx.shape == (12, 3, 32, 32)
    assert ty.shape == (100,) and ty.dtype == np.int32
    # bit-exact roundtrip of batch 1's first image through /255 + normalize
    want = raw[1][0][0].reshape(3, 32, 32).astype(np.float32) / 255.0
    want = (want - cifar10.MEAN) / cifar10.STD
    np.testing.assert_allclose(tx[0], want, rtol=1e-6)
    np.testing.assert_array_equal(ty[:20], np.asarray(raw[1][1], np.int32))
    np.testing.assert_array_equal(vy, np.asarray(test_raw[1], np.int32))


def _write_idx_images(path, arr, gz=False):
    op = gzip.open if gz else open
    with op(path, "wb") as f:
        f.write(struct.pack(">HBB", 0, 8, arr.ndim))
        for dim in arr.shape:
            f.write(struct.pack(">I", dim))
        f.write(arr.tobytes())


def test_mnist_real_parse(tmp_path, loaders, monkeypatch):
    _, mnist = loaders
    rng = np.random.RandomState(0)
    timg = rng.randint(0, 256, (30, 28, 28), dtype=np.uint8)
    tlab = rng.randint(0, 10, (30,)).astype(np.uint8)
    vimg = rng.randint(0, 256, (10, 28, 28), dtype=np.uint8)
    vlab = rng.randint(0, 10, (10,)).astype(np.uint8)
    # train files gzipped, val files raw: both suffix branches parse
    _write_idx_images(str(tmp_path / "train-images-idx3-ubyte.gz"), timg,
                      gz=True)
    _write_idx_images(str(tmp_path / "train-labels-idx1-ubyte.gz"), tlab,
                      gz=True)
    _write_idx_images(str(tmp_path / "t10k-images.idx3-ubyte"), vimg)
    _write_idx_images(str(tmp_path / "t10k-labels.idx1-ubyte"), vlab)
    monkeypatch.setattr(mnist, "SEARCH_DIRS", [str(tmp_path)])

    tx, ty, vx, vy = mnist.load()
    assert mnist.last_load_synthetic is False
    assert tx.shape == (30, 1, 28, 28) and tx.dtype == np.float32
    assert vx.shape == (10, 1, 28, 28)
    np.testing.assert_allclose(tx[:, 0], timg.astype(np.float32) / 255.0)
    np.testing.assert_array_equal(ty, tlab.astype(np.int32))
    np.testing.assert_allclose(vx[:, 0], vimg.astype(np.float32) / 255.0)
    np.testing.assert_array_equal(vy, vlab.astype(np.int32))


def test_synthetic_fallback_sets_flag(tmp_path, loaders, monkeypatch):
    cifar10, mnist = loaders
    monkeypatch.setattr(cifar10, "SEARCH_DIRS", [str(tmp_path / "nope")])
    monkeypatch.setattr(mnist, "SEARCH_DIRS", [str(tmp_path / "nope")])
    cifar10.load()
    mnist.load()
    assert cifar10.last_load_synthetic is True
    assert mnist.last_load_synthetic is True
