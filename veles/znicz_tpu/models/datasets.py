"""Dataset acquisition for the samples.

The reference samples download MNIST/CIFAR from the network [U]. This
environment has zero egress, so each ``load_*`` looks for the real
dataset under ``root.common.dirs.datasets`` first and otherwise
generates a **deterministic synthetic stand-in** with the same shapes
and class structure (seeded class prototypes + noise). Convergence and
numpy↔XLA parity — the properties BASELINE.json tracks — are fully
exercised either way; accuracy numbers on synthetic data are not
comparable to the real dataset and are labelled as such.
"""

import gzip
import hashlib
import logging
import os
import struct

import numpy

from veles import prng
from veles.config import root

logger = logging.getLogger("veles.datasets")

#: provenance of the LAST load per dataset key — which source fed the
#: numbers, real or synthetic data
_PROVENANCE = {}


def data_provenance(key=None):
    """{"source": "real"|"synthetic", "dir": ..., "checksum": ...} of
    the last ``load_<key>`` call (or the whole registry)."""
    if key is None:
        return dict(_PROVENANCE)
    return _PROVENANCE.get(key, {"source": "unloaded"})


def _record(key, source, **extra):
    _PROVENANCE[key] = dict(source=source, **extra)
    # loud by design: every run states which data fed it
    logger.warning("dataset %s: %s%s", key, source.upper(),
                   "".join(" %s=%s" % kv for kv in extra.items()))


#: canonical MNIST idx md5s (uncompressed / .gz), for labelling only —
#: non-canonical files still load if structurally valid, but the
#: provenance says so
_MNIST_MD5 = {
    "train-images-idx3-ubyte": "6bbc9ace898e44ae57da46a324031adb",
    "train-labels-idx1-ubyte": "a25bea736e30d166cdddb491f175f624",
    "t10k-images-idx3-ubyte": "2646ac647ad5339dbf082846283269ea",
    "t10k-labels-idx1-ubyte": "27ae3e4e09519cfbb04c329615203637",
    "train-images-idx3-ubyte.gz": "f68b3c2dcbeaaa9fbdd348bbdeb94873",
    "train-labels-idx1-ubyte.gz": "d53e105ee54ea40749a09fcbcd1e9432",
    "t10k-images-idx3-ubyte.gz": "9fb629c4189551a2d022fa330f9573f3",
    "t10k-labels-idx1-ubyte.gz": "ec29112dd5afa0611ce80d1b7f02629c",
}


def _md5(path):
    h = hashlib.md5()
    with open(path, "rb") as f:
        for chunk in iter(lambda: f.read(1 << 20), b""):
            h.update(chunk)
    return h.hexdigest()


# -- real MNIST (idx files), if present -------------------------------------

def _read_idx(path):
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rb") as f:
        magic, = struct.unpack(">i", f.read(4))
        ndim = magic & 0xFF
        dtype_code = (magic >> 8) & 0xFF
        if (magic >> 16) or dtype_code != 0x08:
            raise ValueError(
                "%s: not a ubyte idx file (magic 0x%08x)"
                % (path, magic))
        shape = struct.unpack(">" + "i" * ndim, f.read(4 * ndim))
        data = numpy.frombuffer(f.read(), dtype=numpy.uint8)
    n = int(numpy.prod(shape, dtype=numpy.int64))
    if data.size != n:
        raise ValueError("%s: idx payload %d != header %s"
                         % (path, data.size, shape))
    return data.reshape(shape)


def _find_mnist_dir():
    cands = [os.path.join(root.common.dirs.datasets, "MNIST"),
             root.common.dirs.datasets]
    for d in cands:
        for suffix in ("", ".gz"):
            if os.path.exists(os.path.join(
                    d, "train-images-idx3-ubyte" + suffix)):
                return d
    return None


def load_mnist(n_train=6000, n_valid=1000):
    """(train_x, train_y, test_x, test_y) floats in [0,1]; real data if
    on disk, synthetic otherwise. Sizes CAP both sources, so configs
    and tests behave the same whether or not idx files are present."""
    d = _find_mnist_dir()
    if d is not None:
        checks = []

        def rd(stem):
            for suffix in ("", ".gz"):
                p = os.path.join(d, stem + suffix)
                if os.path.exists(p):
                    want = _MNIST_MD5.get(stem + suffix)
                    checks.append(_md5(p) == want if want else False)
                    return _read_idx(p)
            raise FileNotFoundError(stem)
        try:
            tx = rd("train-images-idx3-ubyte") \
                .astype(numpy.float32) / 255.0
            ty = rd("train-labels-idx1-ubyte").astype(numpy.int32)
            vx = rd("t10k-images-idx3-ubyte") \
                .astype(numpy.float32) / 255.0
            vy = rd("t10k-labels-idx1-ubyte").astype(numpy.int32)
            if tx.ndim != 3 or len(tx) != len(ty) \
                    or len(vx) != len(vy) or ty.max() > 9 \
                    or vy.max() > 9:
                raise ValueError("inconsistent idx structure")
        except (ValueError, FileNotFoundError) as exc:
            logger.warning("dataset mnist: %s looks real but failed "
                           "validation (%s) — falling back to the "
                           "synthetic stand-in", d, exc)
        else:
            _record("mnist", "real", dir=d,
                    checksum="canonical" if all(checks)
                    else "NON-CANONICAL (structurally valid)")
            return (tx[:n_train], ty[:n_train],
                    vx[:n_valid], vy[:n_valid])
    _record("mnist", "synthetic")
    return synthetic_images(n_train=n_train, n_valid=n_valid,
                            shape=(28, 28), n_classes=10,
                            key="mnist_synth")


# -- synthetic generators ---------------------------------------------------

def synthetic_images(n_train, n_valid, shape, n_classes, key,
                     channels=None, noise=0.35):
    """Class-prototype images + Gaussian noise. Deterministic per key.

    Prototypes are smooth random fields (low-frequency), so nearby
    pixels correlate like strokes do; classes are linearly separable
    but not trivially so once noise is added.
    """
    gen = prng.get(key)
    full_shape = shape if channels is None else (channels,) + shape
    protos = []
    for _ in range(n_classes):
        base = gen.normal(0.0, 1.0, full_shape, numpy.float32)
        protos.append(_smooth(base))
    protos = numpy.stack(protos)

    def make(n):
        labels = gen.randint(0, n_classes, n).astype(numpy.int32)
        x = protos[labels] + gen.normal(
            0.0, noise, (n,) + protos.shape[1:], numpy.float32)
        x = (x - x.min()) / max(x.max() - x.min(), 1e-6)
        return x.astype(numpy.float32), labels

    tx, ty = make(n_train)
    vx, vy = make(n_valid)
    return tx, ty, vx, vy


def _smooth(img):
    """Cheap separable box blur ×2 along the trailing two axes."""
    for axis in (-2, -1):
        for _ in range(2):
            img = (numpy.roll(img, 1, axis) + img
                   + numpy.roll(img, -1, axis)) / 3.0
    return img


def load_cifar10():
    """(train_x, train_y, test_x, test_y), x in CHW float [0,1]."""
    d = os.path.join(root.common.dirs.datasets, "cifar-10-batches-bin")
    if os.path.isdir(d):
        try:
            xs, ys = [], []
            for i in range(1, 6):
                x, y = _read_cifar_bin(
                    os.path.join(d, "data_batch_%d.bin" % i))
                xs.append(x)
                ys.append(y)
            tx = numpy.concatenate(xs)
            ty = numpy.concatenate(ys)
            vx, vy = _read_cifar_bin(os.path.join(d, "test_batch.bin"))
        except (OSError, ValueError) as exc:
            logger.warning("dataset cifar10: %s looks real but failed "
                           "validation (%s) — falling back to the "
                           "synthetic stand-in", d, exc)
        else:
            # no canonical per-.bin md5s exist (the published checksum
            # covers the tarball); record-structure validation is the
            # integrity check here
            _record("cifar10", "real", dir=d,
                    checksum="structural (record size + label range)")
            return tx, ty, vx, vy
    _record("cifar10", "synthetic")
    return synthetic_images(n_train=5000, n_valid=1000, shape=(32, 32),
                            channels=3, n_classes=10, key="cifar_synth")


def _read_cifar_bin(path):
    raw = numpy.fromfile(path, dtype=numpy.uint8)
    if raw.size == 0 or raw.size % 3073:
        raise ValueError("%s: size %d is not a multiple of the "
                         "3073-byte CIFAR record" % (path, raw.size))
    raw = raw.reshape(-1, 3073)
    labels = raw[:, 0].astype(numpy.int32)
    if labels.max() > 9:
        raise ValueError("%s: label %d out of range"
                         % (path, int(labels.max())))
    images = raw[:, 1:].reshape(-1, 3, 32, 32).astype(numpy.float32) / 255.
    return images, labels
