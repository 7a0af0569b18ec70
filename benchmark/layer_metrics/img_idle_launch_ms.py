"""``idle_launch_ms`` of a cell judged on ``train_images_per_s``."""

from benchmark.layer_metrics.idle_launch_ms import read  # noqa: F401
