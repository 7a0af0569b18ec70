"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> \
        --seconds <run_seconds> --trace <0|1>

Resolves the cell by name: ``BENCHMARK.json`` ``workloads`` ->
``configs/<config>.json`` + ``traffic/<traffic>.json`` ->
``drivers/<kind>.py`` (the traffic file's ``kind``) and, for the traced
run, ``layer_metrics/<name>.py`` for every per-layer metric the manifest
lists for the cell. Nothing here knows a cell, a configuration or a
metric by name, so adding one is new files and new manifest entries.

The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` and, traced,
``breakdown``); everything a person wants to read is printed before it.
Without the chip the cell asks for, the exit code is 2 and no result is
printed.
"""

import time

T_PROCESS_START = time.perf_counter()   # before the heavy imports

import argparse                 # noqa: E402
import json                     # noqa: E402
import os                       # noqa: E402
import sys                      # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(BENCH_DIR)
if CHECKOUT not in sys.path:
    sys.path.insert(0, CHECKOUT)

from benchmark.harness import (     # noqa: E402
    NoChip, load_json, load_module)


def metrics_of(manifest, section, cell_name):
    """Entries of ``end_to_end`` or ``per_layer`` that the cell
    reports: those without a ``workloads`` list, and those whose list
    names it."""
    return [m for m in manifest[section]
            if "workloads" not in m or cell_name in m["workloads"]]


def resolve(bench_dir, workload):
    """-> the cell as one dict: its manifest entry, configuration,
    traffic, and the metric entries it reports."""
    manifest = load_json(os.path.dirname(bench_dir), "BENCHMARK.json")
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise SystemExit("no workload %r in BENCHMARK.json (have: %s)"
                         % (workload, ", ".join(sorted(cells))))
    cell = dict(cells[workload])
    configs = {c["name"]: c for c in manifest["configs"]}
    root = os.path.dirname(bench_dir)
    cell["config"] = load_json(root, configs[cell["config"]]["file"])
    cell["traffic_name"] = cell["traffic"]
    cell["traffic"] = load_json(bench_dir, "traffic",
                                cell["traffic_name"] + ".json")
    cell["end_to_end"] = metrics_of(manifest, "end_to_end", workload)
    cell["per_layer"] = metrics_of(manifest, "per_layer", workload)
    cell["bench_dir"] = bench_dir
    return cell


def read_layer_metrics(cell, ctx):
    """{name: value} of the cell's per-layer metrics; a reader that
    finds nothing to read returns None and its metric is left out."""
    out = {}
    for entry in cell["per_layer"]:
        reader = load_module(cell["bench_dir"], "layer_metrics",
                             entry["name"])
        value = reader.read(ctx)
        if value is not None:
            out[entry["name"]] = {"value": float(value),
                                  "unit": entry["unit"]}
    return out


def run_cell(bench_dir, workload, seed, seconds, trace, platform="tpu",
             overrides=None):
    """Run one cell; -> the result object. ``platform`` and
    ``overrides`` exist for the CPU rehearsals under ``tests/`` (a tiny
    preset merged over the configuration's ``model`` and the traffic);
    the command line always measures on the TPU at the files' sizes."""
    cell = resolve(bench_dir, workload)
    for key, patch in (overrides or {}).items():
        target = cell["config"]["model"] if key == "model" \
            else cell["traffic"]
        target.update(patch)
    driver = load_module(bench_dir, "drivers", cell["traffic"]["kind"])
    outcome = driver.run(cell, seed=seed, seconds=seconds,
                         trace=bool(trace), platform=platform,
                         t_process_start=T_PROCESS_START)
    if trace:
        metrics = read_layer_metrics(cell, outcome["ctx"])
    else:
        units = {m["name"]: m["unit"] for m in cell["end_to_end"]}
        metrics = {name: {"value": float(outcome["end_to_end"][name]),
                          "unit": unit}
                   for name, unit in units.items()}
    result = {"correct": bool(outcome["correct"]),
              "attempted": int(outcome["attempted"]),
              "failed": int(outcome["failed"]),
              "metrics": metrics, "device": outcome["device"]}
    if trace and outcome.get("breakdown"):
        result["breakdown"] = outcome["breakdown"]
    return result


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)
    os.chdir(CHECKOUT)      # the program's workflow paths are relative
    try:
        result = run_cell(BENCH_DIR, args.workload, args.seed,
                          args.seconds, args.trace)
    except NoChip as exc:
        print("benchmark: %s" % exc, file=sys.stderr)
        return 2
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
