"""The additive contract: a configuration, a traffic mix (so a cell) and
a per-layer metric dropped into a copy of the benchmark as NEW files and
NEW manifest entries are found and run, with no existing file edited."""

import filecmp
import json
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.dirname(HERE)
CHECKOUT = os.path.dirname(BENCH_DIR)
sys.path.insert(0, CHECKOUT)

from benchmark.tests.test_cells_cpu import ENV, run_cpu_cell   # noqa: E402

NEW_METRIC = '''"""Epochs the program fused into one dispatch."""


def read(ctx):
    return ctx.dispatches[-1]["epochs"] if ctx.dispatches else None
'''


def test_new_cell_config_and_metric_are_files_and_entries(tmp_path):
    copy = tmp_path / "benchmark"
    shutil.copytree(BENCH_DIR, copy,
                    ignore=shutil.ignore_patterns("__pycache__"))
    # the program itself is not part of the benchmark: link it in
    os.symlink(os.path.join(CHECKOUT, "veles"), tmp_path / "veles")

    # a configuration: its own file of sizes (tiny, for the CPU)
    config = json.load(open(copy / "configs" / "lm110m.json"))
    config["name"] = "lm_toy"
    config["model"].update(dim=32, heads=2, head_dim=16, layers=1,
                           ffn_hidden=64, vocab=32, attn_block=16)
    (copy / "configs" / "lm_toy.json").write_text(json.dumps(config))
    # a traffic mix: a data file the one driver reads
    traffic = {"kind": "train", "seq_len": 32, "minibatch": 4,
               "n_train": 16, "n_valid": 4, "learning_rate": 0.05,
               "check": {"forward_tolerance": 1e-4, "loss_falls": False}}
    (copy / "traffic" / "toy_job.json").write_text(json.dumps(traffic))
    # a per-layer metric: a reader of its own
    (copy / "layer_metrics" / "epochs_per_dispatch.py").write_text(
        NEW_METRIC)

    manifest = json.load(open(os.path.join(CHECKOUT, "BENCHMARK.json")))
    manifest["configs"].append({
        "name": "lm_toy", "source": config["source"],
        "file": "benchmark/configs/lm_toy.json", "reduced": [],
        "why": "additivity test"})
    manifest["workloads"].append({
        "name": "lm_toy_job", "config": "lm_toy", "traffic": "toy_job",
        "chips": 1, "why": "additivity test"})
    manifest["per_layer"].append({
        "name": "epochs_per_dispatch", "unit": "epochs",
        "better": "higher", "source": "program_span",
        "layer": "host_step_dispatch", "moves": "train_tokens_per_s",
        "workloads": ["lm_toy_job"]})
    for metric in manifest["end_to_end"] + manifest["per_layer"]:
        if metric["name"] in ("train_tokens_per_s", "step_ms"):
            metric["workloads"].append("lm_toy_job")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(manifest))

    env = dict(ENV, PYTHONPATH=str(tmp_path))
    result, out = run_cpu_cell("lm_toy_job", trace=0,
                               bench_dir=str(copy), env=env)
    assert result["correct"] is True, out[-2000:]
    assert set(result["metrics"]) == {"train_tokens_per_s", "setup_s"}
    result, out = run_cpu_cell("lm_toy_job", trace=1,
                               bench_dir=str(copy), env=env)
    assert set(result["metrics"]) == {"epochs_per_dispatch", "step_ms"}
    assert result["metrics"]["epochs_per_dispatch"]["value"] >= 1

    # nothing that was there has changed
    compare = filecmp.dircmp(BENCH_DIR, copy, ignore=["__pycache__"])
    stack = [compare]
    while stack:
        node = stack.pop()
        assert not node.diff_files and not node.left_only, \
            (node.diff_files, node.left_only)
        stack.extend(node.subdirs.values())
