"""``idle_build_ms`` of a cell judged on ``train_images_per_s``."""

from benchmark.layer_metrics.idle_build_ms import read  # noqa: F401
