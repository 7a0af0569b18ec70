"""``train_mfu`` of a cell judged on ``train_images_per_s``: a per-layer
metric names the one end-to-end metric it moves, so the image cells
report the same reading under a name of their own."""

from benchmark.layer_metrics.train_mfu import read  # noqa: F401
