"""``solver_update_share`` of a cell judged on ``train_images_per_s``."""

from benchmark.layer_metrics.solver_update_share import read  # noqa: F401
