"""Real-dataset auto-ingest (VERDICT r2 item 4): staging idx/binary
files under root.common.dirs.datasets switches every loader off the
synthetic stand-ins with ZERO code changes; provenance records the
source + validation level so every number names its data."""

import gzip
import struct

import numpy
import pytest

import veles.prng as prng
from veles.config import root
from veles.znicz_tpu.models import datasets


@pytest.fixture()
def staged_datasets(tmp_path, monkeypatch):
    """A tiny-but-structurally-valid MNIST idx + CIFAR-10 binary tree."""
    monkeypatch.setattr(root.common.dirs, "datasets", str(tmp_path))
    gen = numpy.random.Generator(numpy.random.PCG64(99))
    mnist = tmp_path / "MNIST"
    mnist.mkdir()

    def write_idx(path, arr):
        ndim = arr.ndim
        head = struct.pack(">i", 0x0800 + ndim)
        head += struct.pack(">" + "i" * ndim, *arr.shape)
        opener = gzip.open if str(path).endswith(".gz") else open
        with opener(path, "wb") as f:
            f.write(head + arr.astype(numpy.uint8).tobytes())

    timg = gen.integers(0, 255, (64, 28, 28), dtype=numpy.uint8)
    tlab = (numpy.arange(64) % 10).astype(numpy.uint8)
    vimg = gen.integers(0, 255, (32, 28, 28), dtype=numpy.uint8)
    vlab = (numpy.arange(32) % 10).astype(numpy.uint8)
    write_idx(mnist / "train-images-idx3-ubyte", timg)
    write_idx(mnist / "train-labels-idx1-ubyte", tlab)
    # mixed compression: the gz path must work too
    write_idx(mnist / "t10k-images-idx3-ubyte.gz", vimg)
    write_idx(mnist / "t10k-labels-idx1-ubyte.gz", vlab)

    cifar = tmp_path / "cifar-10-batches-bin"
    cifar.mkdir()
    for name, n in [("data_batch_%d.bin" % i, 20) for i in
                    range(1, 6)] + [("test_batch.bin", 10)]:
        rec = numpy.zeros((n, 3073), numpy.uint8)
        rec[:, 0] = numpy.arange(n) % 10
        rec[:, 1:] = gen.integers(0, 255, (n, 3072), dtype=numpy.uint8)
        (cifar / name).write_bytes(rec.tobytes())
    return {"mnist_train_images": timg, "cifar_n_train": 100}


def test_mnist_prefers_staged_real_data(staged_datasets):
    tx, ty, vx, vy = datasets.load_mnist(n_train=50, n_valid=20)
    prov = datasets.data_provenance("mnist")
    assert prov["source"] == "real"
    assert "NON-CANONICAL" in prov["checksum"]  # fixture != real MNIST
    assert tx.shape == (50, 28, 28)
    # the actual staged bytes, not synthetic ones
    want = staged_datasets["mnist_train_images"][:50] / 255.0
    assert numpy.allclose(tx, want)
    assert vy.shape == (20,) and vy.max() <= 9


def test_cifar_prefers_staged_real_data(staged_datasets):
    tx, ty, vx, vy = datasets.load_cifar10()
    prov = datasets.data_provenance("cifar10")
    assert prov["source"] == "real"
    assert tx.shape == (staged_datasets["cifar_n_train"], 3, 32, 32)
    assert vx.shape[0] == 10


def test_corrupt_staged_data_falls_back(tmp_path, monkeypatch):
    """A present-but-invalid file must not poison training: loud
    fallback to synthetic."""
    monkeypatch.setattr(root.common.dirs, "datasets", str(tmp_path))
    mnist = tmp_path / "MNIST"
    mnist.mkdir()
    (mnist / "train-images-idx3-ubyte").write_bytes(b"garbage-bytes")
    tx, ty, vx, vy = datasets.load_mnist(n_train=30, n_valid=10)
    assert datasets.data_provenance("mnist")["source"] == "synthetic"
    assert tx.shape == (30, 28 * 28) or tx.shape == (30, 28, 28)


def test_workflow_trains_on_staged_real_data(staged_datasets):
    """The whole point: the SAME workflow code trains on the staged
    real tree, no config or code changes."""
    prng.seed_all(11)
    from veles.znicz_tpu.models import mnist
    saved = root.mnist.loader.to_dict()
    root.mnist.loader.update({"n_train": 60, "n_valid": 20,
                              "minibatch_size": 20})
    root.mnist.decision.max_epochs = 2
    try:
        wf = mnist.create_workflow(name="RealDataMnist")
        wf.initialize(device="cpu")
        wf.run()
    finally:
        root.mnist.loader.update(saved)
    assert datasets.data_provenance("mnist")["source"] == "real"
    assert wf.end_point.reached
    assert len(wf.decision.history) == 2


def test_bench_json_carries_data_tag(staged_datasets):
    """The provenance record labels which data fed a run."""
    datasets.load_mnist(n_train=30, n_valid=10)
    tags = {k: v.get("source")
            for k, v in datasets.data_provenance().items()}
    assert tags.get("mnist") == "real"


def _png_bytes(gen):
    """A tiny valid PNG (the loaders only need decodable files)."""
    import io
    from PIL import Image
    img = Image.fromarray(
        gen.integers(0, 255, (8, 8, 3), dtype=numpy.uint8))
    buf = io.BytesIO()
    img.save(buf, "PNG")
    return buf.getvalue()


def test_imagenet_prep_stages_ilsvrc_archives(tmp_path, monkeypatch):
    """imagenet_prep turns raw-ILSVRC-shaped archives (train tar of
    per-class tars; flat val tar + ground truth + synsets) into the
    class tree models/imagenet.py auto-ingests."""
    import io
    import tarfile
    from veles.znicz_tpu.models import imagenet_prep

    gen = numpy.random.Generator(numpy.random.PCG64(1))
    wnids = ["n01440764", "n01443537", "n01484850"]

    def add_bytes(tar, name, payload):
        info = tarfile.TarInfo(name)
        info.size = len(payload)
        tar.addfile(info, io.BytesIO(payload))

    # train: outer tar of per-class tars, 2 images each
    train_tar = tmp_path / "train.tar"
    with tarfile.open(train_tar, "w") as outer:
        for wnid in wnids:
            inner_buf = io.BytesIO()
            with tarfile.open(fileobj=inner_buf, mode="w") as inner:
                for i in range(2):
                    add_bytes(inner, "%s_%d.JPEG" % (wnid, i),
                              _png_bytes(gen))
            add_bytes(outer, wnid + ".tar", inner_buf.getvalue())
    # val: flat tar + 1-based ids in sorted-filename order + synsets
    val_tar = tmp_path / "val.tar"
    with tarfile.open(val_tar, "w") as tar:
        for i in range(4):
            add_bytes(tar, "ILSVRC2012_val_%08d.JPEG" % (i + 1),
                      _png_bytes(gen))
    labels = tmp_path / "gt.txt"
    labels.write_text("1\n3\n2\n1\n")
    # devkit ILSVRC2012_ID ordering is NOT alphabetical-by-wnid;
    # the fixture mirrors that (and stage_val rejects sorted lists)
    devkit_order = [wnids[1], wnids[0], wnids[2]]
    synsets = tmp_path / "synsets.txt"
    synsets.write_text("".join("%s desc %d\n" % (w, i)
                               for i, w in enumerate(devkit_order)))

    out = tmp_path / "datasets" / "ImageNet"
    n = imagenet_prep.stage_train(str(train_tar), str(out),
                                  log=lambda *a: None)
    assert n == 3
    # resume: second run stages nothing new
    assert imagenet_prep.stage_train(str(train_tar), str(out),
                                     log=lambda *a: None) == 0
    # a PARTIAL class (interrupted extraction) must be re-staged, not
    # skipped as complete
    import shutil
    shutil.move(str(out / "n01440764"),
                str(out / "n01440764.partial"))
    (out / "n01440764.partial" / "n01440764_1.JPEG").unlink()
    assert imagenet_prep.stage_train(str(train_tar), str(out),
                                     log=lambda *a: None) == 1
    assert len(list((out / "n01440764").iterdir())) == 2
    # validation stages into a SEPARATE tree: official val images must
    # not leak into the training split the loader carves from --out
    val_out = tmp_path / "datasets" / "ImageNet-val"
    # an alphabetically-sorted synset list is the signature of the
    # wnid-sorted synset_words.txt, whose line order does NOT match
    # the devkit ids the ground truth indexes — refuse it loudly
    sorted_synsets = tmp_path / "synsets_sorted.txt"
    sorted_synsets.write_text("".join("%s desc\n" % w for w in wnids))
    with pytest.raises(ValueError, match="alphabetical order"):
        imagenet_prep.stage_val(str(val_tar), str(labels),
                                str(sorted_synsets), str(val_out),
                                log=lambda *a: None)
    staged = imagenet_prep.stage_val(str(val_tar), str(labels),
                                     str(synsets), str(val_out),
                                     log=lambda *a: None)
    assert staged == 4
    # ids resolve through the DEVKIT order: id 1 -> devkit_order[0]
    assert len(list((val_out / devkit_order[0]).iterdir())) == 2
    assert len(list((val_out / devkit_order[2]).iterdir())) == 1
    assert len(list((val_out / devkit_order[1]).iterdir())) == 1
    for wnid, count in [("n01440764", 2), ("n01443537", 2),
                        ("n01484850", 2)]:
        assert len(list((out / wnid).iterdir())) == count
    assert sum(len(list(d.iterdir()))
               for d in val_out.iterdir()) == 4

    # the staged tree is exactly what models/imagenet.py auto-ingests
    monkeypatch.setattr(root.common.dirs, "datasets",
                        str(tmp_path / "datasets"))
    from veles.znicz_tpu.models import imagenet
    base, classes = imagenet._real_tree()
    assert base == str(out)
    assert classes == 3


def test_imagenet_prep_rejects_mismatched_ground_truth(tmp_path):
    import io
    import tarfile
    from veles.znicz_tpu.models import imagenet_prep
    gen = numpy.random.Generator(numpy.random.PCG64(2))
    val_tar = tmp_path / "val.tar"
    with tarfile.open(val_tar, "w") as tar:
        payload = _png_bytes(gen)
        info = tarfile.TarInfo("ILSVRC2012_val_00000001.JPEG")
        info.size = len(payload)
        tar.addfile(info, io.BytesIO(payload))
    (tmp_path / "gt.txt").write_text("1\n2\n")     # 2 labels, 1 image
    (tmp_path / "synsets.txt").write_text("n01440764 fish\n")
    with pytest.raises(ValueError, match="1 images but"):
        imagenet_prep.stage_val(
            str(val_tar), str(tmp_path / "gt.txt"),
            str(tmp_path / "synsets.txt"), str(tmp_path / "out"),
            log=lambda *a: None)
