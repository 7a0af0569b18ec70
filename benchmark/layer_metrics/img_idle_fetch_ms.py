"""``idle_fetch_ms`` of a cell judged on ``train_images_per_s``."""

from benchmark.layer_metrics.idle_fetch_ms import read  # noqa: F401
