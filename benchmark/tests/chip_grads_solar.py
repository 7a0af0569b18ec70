"""The parameters' change over ONE optimizer step of the Solar-Open2
cell, program against reference, at the timed sizes on the chip.

    python3 benchmark/tests/chip_grads_solar.py --seed <n> \
        [--controls 1] [--faults 1] [--out <file>]

The cell's own ``correct`` compares losses, and on a corpus of eight
random sequences a loss hardly feels a gradient (PERF.md sections 6 and
7). This run compares what a step DID, as ``chip_grads_lfm2.py`` and
``chip_grads_ouro.py`` do for their cells: the program trains the
cell's configuration for one epoch of one step through ``python -m
veles``' entry point; the reference takes the same weights and the same
sequence through its own gradient and momentum SGD in float32. Momentum
starts at zero, so a parameter's change over the step is minus the
learning rate times its gradient, and

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

* ``beta_unscaled``: ``b = sigmoid(.)``, without the factor 2 of
  ``kda_allow_neg_eigval``;
* ``decay_dropped``: ``a = 0``, the decay gate left out;
* ``state_bf16``: the recurrence's state rounded to bf16 after every
  token;
* ``shared_grad_zeroed``: the shared experts' weights take no step (the
  sound change with those parameters' part set to zero: no run);
* ``gates_detached``: no gradient flows through the delta-rule layers'
  and the attention layer's output gates.

The last line of standard output is one JSON object. Not a cell: its
numbers go to PERF.md by hand, and ``BENCHMARK.json`` does not list it.
``--tiny 1 --platform cpu`` rehearses the control flow at the CPU
preset of ``cpu_cell_solar.py``.
"""

import time

T0 = time.perf_counter()

import argparse                 # noqa: E402
import json                     # noqa: E402
import os                       # noqa: E402
import sys                      # noqa: E402

import numpy                    # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.dirname(HERE)
CHECKOUT = os.path.dirname(BENCH_DIR)
sys.path[:0] = [CHECKOUT, HERE]

from benchmark import harness, run              # noqa: E402
import chip_grads_lfm2 as base                  # noqa: E402
from chip_grads_ouro import fp8_scaled          # noqa: E402

CELL = "solar_open2_250b_s4k_train"
FAULTS = {"beta_unscaled": {"beta_scale": 1.0},
          "decay_dropped": {"no_decay": True},
          "state_bf16": {"state_dtype": "bfloat16"},
          "gates_detached": {"detach_gates": True}}


def one_step_cell(tiny):
    cell = run.resolve(BENCH_DIR, CELL)
    if tiny:
        import cpu_cell_solar
        preset = cpu_cell_solar.cpu_cell.PRESETS[cell["traffic_name"]]
        cell["config"]["model"].update(preset["model"])
        cell["traffic"].update(preset["traffic"])
    traffic = cell["traffic"]
    traffic["n_train"] = traffic["n_valid"] = traffic["minibatch"]
    traffic["check"] = dict(traffic.get("check", {}), train_epochs=1)
    cell["config"]["program"]["overrides"][
        "root.lm.decision.max_epochs"] = 1
    return cell


def reference_step(ref, tree, batches, cell, rounding=None, planted=None):
    """(the parameters' change over the reference's step, its train
    loss), under a rounding of the operands or a planted fault."""
    ref.experiment = dict(planted or {})
    try:
        return base.reference_step(ref, tree, batches, cell, rounding)
    finally:
        ref.experiment = {}


def variant_loss(ref, tree, batch, model, rounding=None, planted=None):
    ref.round_operand, ref.experiment = rounding, dict(planted or {})
    try:
        return ref.loss(tree, batch, model)
    finally:
        ref.round_operand, ref.experiment = None, {}


def without_shared(want):
    """The sound change with the shared experts' part left out."""
    return {path: numpy.zeros_like(w) if "shared" in path else w
            for path, w in want.items()}


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
        for name, planted in FAULTS.items():
            result["validation_loss"][name] = variant_loss(
                ref, tree, initial["valid"], model, planted=planted)
            got, _ = reference_step(ref, tree, initial["train"], cell,
                                    planted=planted)
            result[name] = base.distances(got, want)
            print("%s: %s" % (name, json.dumps(result[name])), flush=True)
        result["shared_grad_zeroed"] = base.distances(
            without_shared(want), want)
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
