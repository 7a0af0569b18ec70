"""The parameters' change over ONE optimizer step of the Laguna cell,
program against reference, at the timed sizes on the chip.

    python3 benchmark/tests/chip_grads_laguna.py --seed <n> \
        [--controls 1] [--faults 1] [--out <file>]

The cell's own ``correct`` compares losses, and on a corpus of four
random sequences a loss hardly feels a gradient - at initialisation
hardly a dropped window either (PERF.md sections 6 and 7). This run
compares what a step DID, as ``chip_grads_solar.py`` does for its cell:
the program trains the cell's configuration for one epoch of one step
through ``python -m veles``' entry point; the reference takes the same
weights and the same sequence through its own gradient and momentum SGD
in float32. Momentum starts at zero, so a parameter's change over the
step is minus the learning rate times its gradient, and

    d = |change(program) - change(reference)| / |change(reference)|

(Euclidean norms over a unit's parameters, and over all of them) is the
relative error of the gradient as the solver applied it. 0 is
agreement; a state left unchanged reads 1.

``--controls 1`` also reads, against the same float32 change, the
reference itself computed with bf16 matmul operands (the precision the
configuration states) and with fp8 (e4m3, scaled per tensor) operands
(the nearest below), and the reference's validation loss under each:
what the cell's loss limits see of a precision.

``--faults 1`` reads what ``d`` gives for a step that did something
else, and what the cell's first validation loss would read. The faults
are planted in the REFERENCE, through its ``experiment`` seam, not in
the program: ``d`` is symmetric in its two sides, and a planted program
would cost a set-up and a compile a fault. Each reads the faulty
reference's change against the sound reference's:

* ``window_dropped``: the sliding layers see the whole causal triangle;
* ``window_513``: a window of 513 for 512 (one key too many a query);
* ``gates_detached``: no gradient flows through the per-head gates;
* ``plain_rope``: the full layers' tables without YaRN (plain
  frequencies, no attention factor);
* ``whole_head``: the full layers rotate the whole head, not half;
* ``group_8_for_9``: query head h of a sliding layer reads K/V head
  ``min(h // 8, 7)``, not ``h // 9``.

The last line of standard output is one JSON object. Not a cell: its
numbers go to PERF.md by hand, and ``BENCHMARK.json`` does not list it.
``--tiny 1 --platform cpu`` rehearses the control flow at the CPU
preset of ``cpu_cell_laguna.py`` (its faults: a window of 41 for 40, a
group of 2 for 3).
"""

import time

T0 = time.perf_counter()

import argparse                 # noqa: E402
import json                     # noqa: E402
import os                       # noqa: E402
import sys                      # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.dirname(HERE)
CHECKOUT = os.path.dirname(BENCH_DIR)
sys.path[:0] = [CHECKOUT, HERE]

from benchmark import harness, run              # noqa: E402
import chip_grads_lfm2 as base                  # noqa: E402
from chip_grads_ouro import fp8_scaled          # noqa: E402
from chip_grads_solar import (                  # noqa: E402
    reference_step, variant_loss)

CELL = "laguna_s_2_1_s8k_train"


def faults(model):
    """{name: what to plant}, one key off the configuration's own."""
    window = model["operators"]["sliding_attention"]["window"]
    heads = model["operators"]["sliding_attention"]["heads"]
    group = heads // model["kv_heads"]
    return {"window_dropped": {"window": "none"},
            "window_%d" % (window + 1): {"window": window + 1},
            "gates_detached": {"detach_gates": True},
            "plain_rope": {"plain_rope": True},
            "whole_head": {"whole_head": True},
            "group_%d_for_%d" % (group - 1, group): {"group": group - 1}}


def one_step_cell(tiny):
    cell = run.resolve(BENCH_DIR, CELL)
    if tiny:
        import cpu_cell_laguna
        preset = cpu_cell_laguna.cpu_cell.PRESETS[cell["traffic_name"]]
        cell["config"]["model"].update(preset["model"])
        cell["traffic"].update(preset["traffic"])
    traffic = cell["traffic"]
    traffic["n_train"] = traffic["n_valid"] = traffic["minibatch"]
    traffic["check"] = dict(traffic.get("check", {}), train_epochs=1)
    cell["config"]["program"]["overrides"][
        "root.lm.decision.max_epochs"] = 1
    return cell


def main():
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--controls", type=int, default=0)
    p.add_argument("--faults", type=int, default=0)
    p.add_argument("--platform", default="tpu")
    p.add_argument("--tiny", type=int, default=0)
    p.add_argument("--out", default="")
    args = p.parse_args()
    os.chdir(CHECKOUT)
    base.T0 = T0
    cell = one_step_cell(args.tiny)
    devices = harness.require_devices(args.platform, cell["chips"])
    initial, after, epoch = base.program_step(cell, args.seed,
                                              args.platform)
    model = cell["config"]["model"]
    ref = harness.load_module(BENCH_DIR, "reference",
                              cell["config"]["reference"])
    tree = ref.from_program(initial["units"], model)
    program = base.changes(tree, ref.from_program(after, model))
    del after
    want, loss = reference_step(ref, tree, initial["train"], cell)
    result = {"seed": args.seed, "device": devices[0].device_kind,
              "train_loss": {"program": epoch["train"]["loss"],
                             "reference": loss},
              "validation_loss": {
                  "program": epoch["validation"]["loss"],
                  "reference": ref.loss(tree, initial["valid"], model)},
              "program": base.distances(program, want)}
    print("program: %s" % json.dumps(result["program"]), flush=True)
    if args.controls:
        import jax.numpy as jnp
        for name, rounding in (
                ("reference_bf16_operands", base.rounded_to(jnp.bfloat16)),
                ("reference_fp8_operands", fp8_scaled)):
            result["validation_loss"][name] = variant_loss(
                ref, tree, initial["valid"], model, rounding=rounding)
            got, _ = reference_step(ref, tree, initial["train"], cell,
                                    rounding)
            result[name] = base.distances(got, want)
            print("%s: %s" % (name, json.dumps(result[name])), flush=True)
    if args.faults:
        for name, planted in faults(model).items():
            result["validation_loss"][name] = variant_loss(
                ref, tree, initial["valid"], model, planted=planted)
            got, _ = reference_step(ref, tree, initial["train"], cell,
                                    planted=planted)
            result[name] = base.distances(got, want)
            print("%s: %s" % (name, json.dumps(result[name])), flush=True)
    print("validation_loss: %s" % json.dumps(result["validation_loss"]),
          flush=True)
    line = json.dumps(result)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            f.write(line + "\n")
    print(line, flush=True)


if __name__ == "__main__":
    main()
