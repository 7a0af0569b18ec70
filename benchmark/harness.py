"""What every driver shares: the device check, the device facts of the
result line, and the context a per-layer metric's reader is given."""

import importlib.util
import json
import os

from benchmark.reduce import peaks


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_module(bench_dir, package, name):
    """``<bench_dir>/<package>/<name>.py`` as a module, found by the
    name a manifest entry or a data file gives."""
    path = os.path.join(bench_dir, package, name + ".py")
    if not os.path.isfile(path):
        raise FileNotFoundError(
            "%s names %r, but there is no %s" % (package, name, path))
    spec = importlib.util.spec_from_file_location(
        "benchmark.%s.%s" % (package, name.replace(".", "_")), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


class NoChip(Exception):
    """The machine does not hold the chips the cell asks for: the
    command exits 2 and prints no result."""


def require_devices(platform, chips):
    """-> jax's devices, after checking that they are ``platform`` and
    at least ``chips`` of them. On a TPU the kind must be in the peak
    table. Nothing continues on another device than the one asked for.
    """
    try:
        import jax
        devices = jax.devices()
    except RuntimeError as exc:        # jax found no backend at all
        raise NoChip("jax found no device: %s" % exc) from None
    if devices[0].platform != platform:
        raise NoChip("jax's devices are %r, the cell needs %r"
                     % (devices[0].platform, platform))
    if len(devices) < chips:
        raise NoChip("the cell needs %d chip(s), jax sees %d"
                     % (chips, len(devices)))
    if platform == "tpu":
        peaks.peaks_of(devices[0].device_kind)     # unknown kind: error
    return devices


def memory_peak_bytes(devices):
    """Peak bytes held on the fullest device, or None where the backend
    does not report it (the CPU rehearsal). On the TPU the allocator
    keeps two books: ``peak_bytes_in_use`` counts arrays (weights,
    optimizer state, the resident corpus) and ``peak_bytes_reserved``
    the scratch memory a running program reserves for its temporaries
    (activations) — on this runtime the first alone read 1.1 GB for a
    step whose ``memory_analysis()`` is 12.8 GB. What the chip holds at
    its fullest is their sum."""
    peaks = []
    for device in devices:
        stats = device.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(stats["peak_bytes_in_use"]
                         + stats.get("peak_bytes_reserved", 0))
    return max(peaks) if peaks else None


def device_facts(devices, peak_bytes):
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices),
            "memory_peak_bytes": peak_bytes}


class Context:
    """What a reader under ``layer_metrics/`` may look at.

    * ``cell``: the resolved cell (manifest entry, ``config``,
      ``traffic``);
    * ``chips``, ``device_kind``, ``peaks`` (None off the TPU);
    * ``dispatches``: the program's ``xla.dispatch.*`` spans that
      completed inside the window, oldest first, each
      ``{"start", "dur", "epochs", "warm"}`` in seconds on the
      program's clock;
    * ``samples_per_epoch``, ``steps_per_epoch``, ``work_per_sample``
      (tokens of a sequence; 1 for an image);
    * ``memory_peak_bytes``;
    * ``trace``: ``reduce.trace.Reduction`` of the profiled dispatches,
      or None when nothing was traced;
    * ``costs``: the configuration's cost module.
    """

    def __init__(self, **fields):
        self.__dict__.update(fields)

    @property
    def span_window(self):
        """(start, end) of the window's complete dispatches."""
        first, last = self.dispatches[0], self.dispatches[-1]
        return first["start"], last["start"] + last["dur"]

    @property
    def span_samples_per_s(self):
        """Samples per second over the window, from the program's own
        spans (per-layer arithmetic; the end-to-end figure is taken on
        the harness's clock)."""
        start, end = self.span_window
        epochs = sum(d["epochs"] for d in self.dispatches)
        return epochs * self.samples_per_epoch / (end - start)


def trace_dir(bench_dir, cell_name):
    """Where a traced run leaves its profile: inside the checkout, in a
    directory ``.gitignore`` lists."""
    return os.path.join(os.path.dirname(bench_dir), ".benchmark_out",
                        "trace_" + cell_name)
