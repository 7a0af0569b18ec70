"""Lint of ``BENCHMARK.json`` against the files it names and the rules
its readers (``run.py``, the driver) rely on."""

import os
import re
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.dirname(HERE)
CHECKOUT = os.path.dirname(BENCH_DIR)
sys.path.insert(0, CHECKOUT)

from benchmark import harness, run      # noqa: E402

MANIFEST = harness.load_json(CHECKOUT, "BENCHMARK.json")
NAME = re.compile(r"^[A-Za-z0-9][A-Za-z0-9_.-]{0,63}$")
#: a layer's name, as the driver's check states it (BENCHMARK_REFUSED, PR 22)
LAYER = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
#: what ``reduced`` may never name
WIDTH = re.compile(r"(hidden|intermediate|latent|state|proj|dim|rank|"
                   r"head_dim|expansion|experts_per_tok|ffn)", re.I)


def test_top_level_keys_and_sizes():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(CHECKOUT, "BENCHMARK.json")) \
        <= 64 << 10
    assert MANIFEST["paths"] == ["benchmark"]
    assert MANIFEST["command"] == ["python3", "benchmark/run.py"]
    assert isinstance(MANIFEST["run_seconds"], int)
    assert 1 <= MANIFEST["run_seconds"] <= 51
    assert 2 <= len(MANIFEST["workloads"]) <= 24
    assert 1 <= len(MANIFEST["configs"]) <= 24
    assert 1 <= len(MANIFEST["end_to_end"]) <= 16
    assert 1 <= len(MANIFEST["per_layer"]) <= 128


def test_names_are_plain_and_used_once():
    names = [e["name"] for section in ("configs", "workloads",
                                       "end_to_end", "per_layer")
             for e in MANIFEST[section]]
    for name in names:
        assert NAME.match(name), name
    assert len(names) == len(set(names))
    for entry in MANIFEST["configs"] + MANIFEST["workloads"]:
        assert len(entry["why"]) <= 200, entry["name"]
    for root, _, files in os.walk(BENCH_DIR):
        if "__pycache__" in root:
            continue
        for name in files:
            rel = os.path.relpath(os.path.join(root, name), CHECKOUT)
            assert re.match(r"^[A-Za-z0-9_./-]+$", rel), rel


def test_every_name_resolves_to_a_file():
    used = set()
    for cell in MANIFEST["workloads"]:
        resolved = run.resolve(BENCH_DIR, cell["name"])
        used.add(cell["config"])
        kind = resolved["traffic"]["kind"]
        assert os.path.isfile(os.path.join(
            BENCH_DIR, "drivers", kind + ".py"))
        for package in ("costs", "reference"):
            assert os.path.isfile(os.path.join(
                BENCH_DIR, package, resolved["config"][package] + ".py"))
    pairs = [(c["config"], c["traffic"]) for c in MANIFEST["workloads"]]
    assert len(pairs) == len(set(pairs))
    files = [c["file"] for c in MANIFEST["configs"]]
    assert len(files) == len(set(files))
    for config in MANIFEST["configs"]:
        assert config["name"] in used, "a configuration no cell uses"
        assert config["file"].startswith("benchmark/")
        doc = harness.load_json(CHECKOUT, config["file"])
        assert doc["source"] == config["source"]
        assert doc["reduced"] == config["reduced"]
        for key in config["reduced"]:
            assert not WIDTH.search(key), key
    for metric in MANIFEST["per_layer"]:
        assert os.path.isfile(os.path.join(
            BENCH_DIR, "layer_metrics", metric["name"] + ".py"))


def test_metrics_of_every_cell():
    end_to_end = {m["name"]: m for m in MANIFEST["end_to_end"]}
    assert end_to_end["setup_s"]["bound"] == 0.1
    cells = {c["name"] for c in MANIFEST["workloads"]}
    for metric in MANIFEST["end_to_end"] + MANIFEST["per_layer"]:
        assert metric["source"] in SOURCES
        assert metric["better"] in ("higher", "lower")
        assert set(metric.get("workloads", cells)) <= cells
        if metric["name"].endswith("_roofline"):
            assert metric["unit"] == "%"
    with open(os.path.join(CHECKOUT, "PERF.md")) as perf:
        layers_in_perf = re.findall(r"^\| `([^`]+)` \|", perf.read(), re.M)
    for metric in MANIFEST["per_layer"]:
        assert LAYER.match(metric["layer"]), metric["name"]
        assert metric["layer"] in layers_in_perf, metric["layer"]
    for metric in MANIFEST["end_to_end"]:
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0 < metric["bound"] <= 0.1
    for cell in MANIFEST["workloads"]:
        mine = {m["name"] for m in run.metrics_of(
            MANIFEST, "end_to_end", cell["name"])}
        assert "setup_s" in mine and len(mine) >= 2, cell["name"]
        layers = run.metrics_of(MANIFEST, "per_layer", cell["name"])
        assert layers, cell["name"]
        for metric in layers:   # reported only where what it moves is
            assert metric["moves"] in mine, (cell["name"], metric["name"])


def test_four_chip_cells_are_at_most_a_quarter():
    chips = [c["chips"] for c in MANIFEST["workloads"]]
    assert set(chips) <= {1, 4}
    assert chips.count(4) <= max(1, len(chips) // 4)


def test_configs_throughput_metric_is_in_the_manifest():
    """The driver reports throughput under the name the configuration's
    ``work`` gives; the manifest must list it for the cell."""
    for cell in MANIFEST["workloads"]:
        resolved = run.resolve(BENCH_DIR, cell["name"])
        names = {m["name"] for m in resolved["end_to_end"]}
        assert resolved["config"]["work"]["metric"] in names
