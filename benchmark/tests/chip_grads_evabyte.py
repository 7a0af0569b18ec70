"""The parameters' change over ONE optimizer step of the EVA byte LM
cell, program against reference, at the timed sizes on the chip.

    python3 benchmark/tests/chip_grads_evabyte.py --seed <n> \
        [--controls 1] [--faults 1] [--losses-only 1 | --epoch 1] \
        [--out <file>]

The cell's own ``correct`` compares losses, and on a corpus of four
random sequences a loss hardly feels a gradient (PERF.md sections 6 and
7). This run compares what a step DID, as ``chip_grads_laguna.py`` does
for its cell: the program trains the cell's configuration for one epoch
of one step through ``python -m veles``' entry point; the reference
takes the same weights and the same sequence through its own gradient
and momentum SGD in float32. Momentum starts at zero, so a parameter's
change over the step is minus the learning rate times its gradient, and

    d = |change(program) - change(reference)| / |change(reference)|

(Euclidean norms over a unit's parameters, and over all of them) is the
relative error of the gradient as the solver applied it. 0 is
agreement; a state left unchanged reads 1.

``--controls 1`` also reads, against the same float32 change, the
reference itself computed with bf16 matmul operands (the precision the
configuration states) and with fp8 (e4m3, scaled per tensor) operands
(the nearest below), and the reference with bf16 operands whose
stream between sub-layers and logits are rounded to bf16 too (where the
configuration keeps them in float32); and the reference's validation
loss under each: what the cell's loss limits see of a precision.

``--faults 1`` reads what ``d`` gives for a step that did something
else, and what the cell's first validation loss would read. The faults
are planted in the REFERENCE, through its ``experiment`` seam, not in
the program: ``d`` is symmetric in its two sides, and a planted program
would cost a set-up and a compile a fault. Each reads the faulty
reference's change against the sound reference's:

* ``remote_dropped``: no summary is seen, the local windows alone;
* ``mean_pool``: the summaries pooled by the chunk's mean, not by mu
  and phi;
* ``window_2049``: a window of 2049 for 2048;
* ``chunk_15``: chunks of 15 for 16;
* ``one_head``: the loss of the next byte's head alone, the 8 heads
  collapsed to one.

``--losses-only 1`` reads, for the controls and the faults, the
validation loss alone (no reference step): what the cell's forward
limit sees of them on a further seed, at a fifth of the time.

``--epoch 1`` reads what the cell's TRAINING limit sees: the program
runs the cell's own first epoch (its four train sequences); the
reference, sound and under each control and fault, trains over the same
sequences from the same weights, and the two numbers the cell's check
compares are read, the mean train loss of epoch 0 and the validation
loss after it, each against the sound reference's. ``d`` is not read.

The last line of standard output is one JSON object. Not a cell: its
numbers go to PERF.md by hand, and ``BENCHMARK.json`` does not list it.
``--tiny 1 --platform cpu`` rehearses the control flow at the CPU
preset of ``cpu_cell_evabyte.py`` (its faults: a window of 33 for 32,
chunks of 3 for 4).
"""

import time

T0 = time.perf_counter()

import argparse                 # noqa: E402
import json                     # noqa: E402
import os                       # noqa: E402
import statistics               # noqa: E402
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

CELL = "evabyte_s16k_train"


def faults(model):
    """{name: what to plant}, one key off the configuration's own."""
    window, chunk = model["eva_window"], model["eva_chunk"]
    return {"remote_dropped": {"no_remote": True},
            "mean_pool": {"mean_pool": True},
            "window_%d" % (window + 1): {"window": window + 1},
            "chunk_%d" % (chunk - 1): {"chunk": chunk - 1},
            "one_head": {"pred_heads": 1}}


def variants(model, controls, planted):
    """[(name, operand rounding, fault)]: the controls, then the
    faults."""
    import jax.numpy as jnp
    bf16 = base.rounded_to(jnp.bfloat16)
    out = []
    if controls:
        out += [("reference_bf16_operands", bf16, None),
                ("reference_fp8_operands", fp8_scaled, None),
                ("reference_bf16_stream", bf16, {"stream_bf16": True})]
    if planted:
        out += [(name, None, fault) for name, fault in faults(model).items()]
    return out


def one_step_cell(tiny, whole_epoch=False):
    """The cell cut to one step (or, ``whole_epoch``, as it runs), one
    epoch long, the training check on."""
    cell = run.resolve(BENCH_DIR, CELL)
    if tiny:
        import cpu_cell_evabyte
        preset = cpu_cell_evabyte.cpu_cell.PRESETS[cell["traffic_name"]]
        cell["config"]["model"].update(preset["model"])
        cell["traffic"].update(preset["traffic"])
    traffic = cell["traffic"]
    if tiny and whole_epoch:
        traffic["n_train"] = 4 * traffic["minibatch"]
    if not whole_epoch:
        traffic["n_train"] = traffic["n_valid"] = traffic["minibatch"]
    traffic["check"] = dict(traffic.get("check", {}), train_epochs=1)
    cell["config"]["program"]["overrides"][
        "root.lm.decision.max_epochs"] = 1
    return cell


def epoch_readings(ref, tree, initial, cell, rounding=None,
                   planted=None):
    """(mean train loss of epoch 0, validation loss after it) of the
    reference under a rounding of the operands or a planted fault: the
    two numbers the cell's training check compares."""
    model, traffic = cell["config"]["model"], cell["traffic"]
    ref.round_operand, ref.experiment = rounding, dict(planted or {})
    try:
        stepped, losses = ref.train(tree, initial["train"], model,
                                    traffic["learning_rate"],
                                    model["gradient_moment"])
        return (statistics.fmean(losses),
                ref.loss(stepped, initial["valid"], model))
    finally:
        ref.round_operand, ref.experiment = None, {}


def epoch(args, cell, ref, model, devices):
    """``--epoch 1``: the training check's two numbers, and the first
    validation loss, for the program, each control and each fault."""
    initial, after, history = base.program_step(cell, args.seed,
                                                args.platform)
    del after
    tree = ref.from_program(initial["units"], model)
    first = ref.loss(tree, initial["valid"], model)
    sound = epoch_readings(ref, tree, initial, cell)
    result = {"seed": args.seed, "device": devices[0].device_kind,
              "reference": {"first_validation_loss": first,
                            "mean_train_loss": sound[0],
                            "validation_loss_after": sound[1]},
              "abs_diff": {"program": {
                  "first_validation_loss": abs(
                      history["validation"]["loss"] - first),
                  "mean_train_loss": abs(history["train"]["loss"]
                                         - sound[0])}}}
    print("program: %s" % json.dumps(result["abs_diff"]["program"]),
          flush=True)
    for name, rounding, planted in variants(model, args.controls,
                                            args.faults):
        loss = variant_loss(ref, tree, initial["valid"], model,
                            rounding=rounding, planted=planted)
        train, valid = epoch_readings(ref, tree, initial, cell,
                                      rounding, planted)
        result["abs_diff"][name] = {
            "first_validation_loss": abs(loss - first),
            "mean_train_loss": abs(train - sound[0]),
            "validation_loss_after": abs(valid - sound[1])}
        print("%s: %s" % (name, json.dumps(result["abs_diff"][name])),
              flush=True)
    return result


def main():
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--controls", type=int, default=0)
    p.add_argument("--faults", type=int, default=0)
    p.add_argument("--platform", default="tpu")
    p.add_argument("--tiny", type=int, default=0)
    p.add_argument("--losses-only", type=int, default=0)
    p.add_argument("--epoch", type=int, default=0)
    p.add_argument("--out", default="")
    args = p.parse_args()
    os.chdir(CHECKOUT)
    base.T0 = T0
    cell = one_step_cell(args.tiny, whole_epoch=args.epoch)
    devices = harness.require_devices(args.platform, cell["chips"])
    model = cell["config"]["model"]
    ref = harness.load_module(BENCH_DIR, "reference",
                              cell["config"]["reference"])
    if args.epoch:
        finish(epoch(args, cell, ref, model, devices), args.out)
        return
    initial, after, history = base.program_step(cell, args.seed,
                                                args.platform)
    tree = ref.from_program(initial["units"], model)
    program = base.changes(tree, ref.from_program(after, model))
    del after
    want, loss = reference_step(ref, tree, initial["train"], cell)
    result = {"seed": args.seed, "device": devices[0].device_kind,
              "train_loss": {"program": history["train"]["loss"],
                             "reference": loss},
              "validation_loss": {
                  "program": history["validation"]["loss"],
                  "reference": ref.loss(tree, initial["valid"], model)},
              "program": base.distances(program, want)}
    print("program: %s" % json.dumps(result["program"]), flush=True)
    for name, rounding, planted in variants(model, args.controls,
                                            args.faults):
        result["validation_loss"][name] = variant_loss(
            ref, tree, initial["valid"], model, rounding=rounding,
            planted=planted)
        if args.losses_only:
            continue
        got, _ = reference_step(ref, tree, initial["train"], cell,
                                rounding, planted)
        result[name] = base.distances(got, want)
        print("%s: %s" % (name, json.dumps(result[name])), flush=True)
    print("validation_loss: %s" % json.dumps(result["validation_loss"]),
          flush=True)
    finish(result, args.out)


def finish(result, out):
    line = json.dumps(result)
    if out:
        os.makedirs(os.path.dirname(out) or ".", exist_ok=True)
        with open(out, "w") as f:
            f.write(line + "\n")
    print(line, flush=True)


if __name__ == "__main__":
    main()
