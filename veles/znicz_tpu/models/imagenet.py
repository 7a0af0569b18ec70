"""ImageNet AlexNet sample — the flagship perf config.

Rebuild of reference ``samples/ImageNet/`` [U] (SURVEY.md §2.8 row 3,
§6: the only hard perf target — AlexNet throughput per chip). One-tower
AlexNet over NHWC: 5 conv blocks (ReLU, cross-map LRN after the first
two, overlapping 3×3/s2 max-pools), two dropout+FC(4096) blocks, and a
softmax classifier.

Data: a real ImageNet directory tree (``<base>/<wnid or class>/*.jpg``)
streamed through :class:`veles.loader.image.AutoLabelFileImageLoader`
when ``root.imagenet.loader.base_dir`` exists; otherwise a
deterministic synthetic stand-in pre-rendered into a device-resident
uint8 bank (zero-egress environment), with crop/mirror/normalize fused
into the compiled step either way.
"""

import os

import numpy

from veles.config import root
from veles.loader.fullbatch import FullBatchLoader
from veles.loader.image import AutoLabelFileImageLoader
from veles.znicz_tpu.standard_workflow import StandardWorkflow


def alexnet_layers(n_classes, lr=0.01, wd=0.0005, moment=0.9):
    gd = {"learning_rate": lr, "weights_decay": wd,
          "gradient_moment": moment}
    return [
        {"type": "conv_relu",
         "->": {"n_kernels": 96, "kx": 11, "ky": 11, "sliding": 4},
         "<-": dict(gd)},
        {"type": "norm", "->": {"n": 5, "alpha": 1e-4, "beta": 0.75,
                                "k": 2.0}},
        {"type": "max_pooling", "->": {"kx": 3, "ky": 3,
                                       "sliding": 2}},
        {"type": "conv_relu",
         "->": {"n_kernels": 256, "kx": 5, "ky": 5, "padding": 2},
         "<-": dict(gd)},
        {"type": "norm", "->": {"n": 5, "alpha": 1e-4, "beta": 0.75,
                                "k": 2.0}},
        {"type": "max_pooling", "->": {"kx": 3, "ky": 3,
                                       "sliding": 2}},
        {"type": "conv_relu",
         "->": {"n_kernels": 384, "kx": 3, "ky": 3, "padding": 1},
         "<-": dict(gd)},
        {"type": "conv_relu",
         "->": {"n_kernels": 384, "kx": 3, "ky": 3, "padding": 1},
         "<-": dict(gd)},
        {"type": "conv_relu",
         "->": {"n_kernels": 256, "kx": 3, "ky": 3, "padding": 1},
         "<-": dict(gd)},
        {"type": "max_pooling", "->": {"kx": 3, "ky": 3,
                                       "sliding": 2}},
        {"type": "dropout", "->": {"dropout_ratio": 0.5}},
        {"type": "all2all_relu", "->": {"output_sample_shape": 4096},
         "<-": dict(gd)},
        {"type": "dropout", "->": {"dropout_ratio": 0.5}},
        {"type": "all2all_relu", "->": {"output_sample_shape": 4096},
         "<-": dict(gd)},
        {"type": "softmax", "->": {"output_sample_shape": n_classes},
         "<-": dict(gd)},
    ]


root.imagenet.update({
    "loader": {"minibatch_size": 128, "base_dir": None,
               "scale": (256, 256), "crop": (227, 227),
               # synthetic stand-in sizing
               "n_classes": 16, "n_train": 2048, "n_valid": 256},
    "decision": {"max_epochs": 10, "fail_iterations": 10},
    "lr": 0.01,
})


class SyntheticImageLoader(FullBatchLoader):
    """Deterministic synthetic image corpus as a DEVICE-RESIDENT uint8
    bank (per-class low-frequency prototypes + per-index noise,
    pre-rendered at scale size). The bank ships to the device ONCE;
    every epoch then runs through the class-scan fast path with
    center-crop + mirror-half + normalization fused INTO the compiled
    step (``xla_batch_transform``), so steady-state throughput measures
    the TPU, not the host-to-device link. A real ImageNet tree still
    streams via AutoLabelFileImageLoader (it cannot be
    device-resident), see ``make_loader``."""

    def __init__(self, workflow, n_classes=16, n_train=2048,
                 n_valid=256, seed=0xA1E7, scale=(256, 256),
                 crop=(227, 227), normalize_mean=0.5,
                 normalize_std=0.5, **kwargs):
        kwargs.pop("mirror", None)   # make_loader passes streaming kw
        super().__init__(workflow, **kwargs)
        self.n_classes = int(n_classes)
        self._n_train = int(n_train)
        self._n_valid = int(n_valid)
        self._seed = int(seed)
        self.scale = tuple(scale)
        self.crop = tuple(crop)
        self.normalize_mean = float(normalize_mean)
        self.normalize_std = float(normalize_std)
        self.serve_dtype = numpy.uint8   # the bank ships as bytes

    def load_data(self):
        self.class_lengths = [0, self._n_valid, self._n_train]
        n = self._n_valid + self._n_train
        gen = numpy.random.Generator(numpy.random.PCG64(self._seed))
        h, w = self.scale
        c = 3
        # low-res prototypes upsampled: distinguishable classes
        small = gen.uniform(0, 255, (self.n_classes, 8, 8, c))
        reps = (h + 7) // 8, (w + 7) // 8
        protos = numpy.kron(
            small, numpy.ones((1, reps[0], reps[1], 1)))[
            :, :h, :w, :].astype(numpy.int16)
        bank = numpy.empty((n, h, w, c), numpy.uint8)
        th, tw = (h + 3) // 4, (w + 3) // 4
        labels = numpy.arange(n) % self.n_classes
        for lo in range(0, n, 256):       # cap transient int16 memory
            hi = min(lo + 256, n)
            noise = gen.integers(-48, 48, (hi - lo, th, tw, c),
                                 dtype=numpy.int16)
            noise = numpy.tile(noise, (1, 4, 4, 1))[:, :h, :w, :]
            numpy.clip(protos[labels[lo:hi]] + noise, 0, 255,
                       out=noise)
            bank[lo:hi] = noise
        self.original_data.mem = bank
        self.original_labels.mem = labels.astype(numpy.int32)

    def label_of(self, index):
        return index % self.n_classes

    def apply_normalization(self):
        # the uint8 bank must stay uint8: crop/normalize is fused into
        # the step (_augment); a pluggable normalizer would corrupt it
        from veles.normalization import NoneNormalizer
        if not isinstance(self.normalizer, NoneNormalizer):
            raise NotImplementedError(
                "%s normalizes on device (_augment); "
                "normalization_type is not supported here"
                % type(self).__name__)

    # -- shared crop/mirror/normalize (device + oracle) ----------------

    def _crop_origin(self):
        ph, pw = self.scale
        ch, cw = self.crop
        return (ph - ch) // 2, (pw - cw) // 2

    def _augment(self, xp, batch, train):
        """uint8 (mb, H, W, C) -> float32 (mb, ch, cw, C): center
        crop, mirror every other row (TRAIN only — eval must see the
        true pixels), normalize. One formula for the traced path and
        the numpy oracle."""
        y, x = self._crop_origin()
        ch, cw = self.crop
        data = batch[:, y:y + ch, x:x + cw, :]
        if train:
            flipped = data[:, :, ::-1, :]
            mask = (xp.arange(data.shape[0]) % 2 == 0)
            data = xp.where(mask[:, None, None, None], flipped, data)
        std = max(self.normalize_std, 1e-6)
        return ((data.astype(xp.float32) / 255.0
                 - self.normalize_mean) / std)

    def xla_batch_transform(self, name, tensor, train=False):
        if name != "data":
            return tensor
        import jax.numpy as jnp
        return self._augment(jnp, tensor, train)

    def create_minibatch_data(self):
        ch, cw = self.crop
        self.minibatch_data.reset(numpy.zeros(
            (self.max_minibatch_size, ch, cw, 3), numpy.float32))
        self.minibatch_labels.reset(numpy.zeros(
            (self.max_minibatch_size,), numpy.int32))

    def fill_minibatch(self):
        idx = self.minibatch_indices.mem
        self.minibatch_data.map_invalidate()
        self.minibatch_data.mem[...] = self._augment(
            numpy, self.original_data.mem[idx],
            train=bool(self.train_phase))
        self.minibatch_labels.map_invalidate()
        self.minibatch_labels.mem[...] = self.original_labels.mem[idx]


def _real_tree():
    """(base_dir, n_classes) of a usable real image tree, or (None, 0).
    ONE definition of both the base-dir fallback and the class-dir
    criterion (a subdir counts only if it holds image files — exactly
    AutoLabelFileImageLoader's rule), shared by the loader factory and
    the softmax-width probe so they can never disagree."""
    from veles.loader.image import IMAGE_EXTS
    base = root.imagenet.loader.get("base_dir") or os.path.join(
        root.common.dirs.datasets, "ImageNet")
    if not (base and os.path.isdir(base)):
        return None, 0
    n = 0
    for entry in os.listdir(base):
        if entry.endswith(".partial"):
            continue   # interrupted imagenet_prep staging, not a class
        sub = os.path.join(base, entry)
        if os.path.isdir(sub) and any(
                f.lower().endswith(IMAGE_EXTS)
                for f in os.listdir(sub)):
            n += 1
    return (base, n) if n else (None, 0)


def make_loader(wf):
    from veles.znicz_tpu.models.datasets import _record
    cfg = root.imagenet.loader
    kwargs = dict(name="loader",
                  minibatch_size=cfg.minibatch_size,
                  scale=tuple(cfg.scale), crop=tuple(cfg.crop),
                  mirror="random")
    base, n = _real_tree()
    if base:
        _record("imagenet", "real", dir=base, classes=n,
                checksum="structural (image-dir tree)")
        return AutoLabelFileImageLoader(wf, base_dir=base, **kwargs)
    _record("imagenet", "synthetic")
    return SyntheticImageLoader(
        wf, n_classes=cfg.n_classes, n_train=cfg.n_train,
        n_valid=cfg.n_valid, **kwargs)


def n_classes_of(loader):
    return getattr(loader, "n_classes", None) or 1000


def _probe_classes():
    """Softmax width BEFORE the loader exists: a real directory tree
    determines its own class count; the synthetic stand-in uses the
    config. Shares make_loader's resolution (see ``_real_tree``)."""
    base, n = _real_tree()
    return n if base else root.imagenet.loader.n_classes


def create_workflow(name="AlexNetWorkflow", **kwargs):
    cfg = root.imagenet
    layers = alexnet_layers(_probe_classes(), lr=cfg.lr)
    return StandardWorkflow(
        None, name=name, layers=layers,
        loader_factory=make_loader,
        decision_config=cfg.decision.to_dict(),
        **kwargs)


def run(load, main):
    load(StandardWorkflow,
         layers=alexnet_layers(_probe_classes(),
                               lr=root.imagenet.lr),
         loader_factory=make_loader,
         decision_config=root.imagenet.decision.to_dict())
    main()
