"""``unscoped_share`` of a cell judged on ``train_images_per_s``."""

from benchmark.layer_metrics.unscoped_share import read  # noqa: F401
