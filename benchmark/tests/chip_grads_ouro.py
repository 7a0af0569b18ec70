"""The parameters' change over ONE optimizer step of the Ouro cell,
program against reference, at the timed sizes on the chip.

    python3 benchmark/tests/chip_grads_ouro.py --seed <n> \
        [--controls 1] [--faults 1] [--out <file>]

The cell's own ``correct`` compares losses, and on a corpus of two
random sequences a loss hardly feels a gradient (PERF.md section 6, PR
28 and 32). This run compares what a step DID, as
``chip_grads_lfm2.py`` does for its cell: the program trains the cell's
configuration for one epoch of one step through ``python -m veles``'
entry point; the reference takes the same weights and the same
minibatch through ``jax.grad`` and momentum SGD in float32. Momentum
starts at zero, so a parameter's change over the step is minus the
learning rate times its gradient, and

    d = |change(program) - change(reference)| / |change(reference)|

(Euclidean norms over a unit's parameters, and over all of them) is the
relative error of the gradient as the solver applied it. 0 is
agreement; a state left unchanged reads 1.

``--controls 1`` also reads, against the same float32 change, the
reference itself computed with bf16 matmul operands (the precision the
configuration states) and with fp8 (e4m3) operands (the nearest below;
scaled per tensor to the format's range as fp8 recipes do, since a
plain cast overflows to NaN here), and the reference's validation loss
under each: what the cell's loss limits see of a precision.

``--faults 1`` reads what ``d`` gives for a step that did something
else. The faults are planted in a TWIN of the reference's gradient
(:func:`twin_gradients`, through the seams ``sequence_gradients``
leaves for them), not in the program: a visit of the program's loop does not know its pass, and
``d`` is symmetric in its two sides — what it reads between a sound
step and a faulty one does not depend on which of them ran on the
kernels. Each reads the faulty twin's change against the sound
reference's:

* ``pass_dropped``: the layers' weights take no gradient from pass 1
  (three visits summed, not four);
* ``four_updates``: the solver runs after every pass on that pass's
  gradient alone (momentum applied four times), not once on the sum;
* ``gate_detached``: the exit weights ``p_t`` are constants in the
  expected loss — the gate learns from the entropy term alone;
* ``logits_bf16``: every exit's logits rounded to bf16 before the
  softmax. On a TPU that is the PROGRAM's own policy (``TokenDense``
  hands its output over in the activation type, as in every accepted
  LM cell), so this reading says what that policy costs, not whether a
  fault would show.

The last line of standard output is one JSON object. Not a cell: its
numbers go to PERF.md by hand, and ``BENCHMARK.json`` does not list it.
``--tiny 1 --platform cpu`` rehearses the control flow at the CPU
preset of ``cpu_cell_ouro.py``.
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

CELL = "ouro_2_6b_s8k_train"
FAULTS = ("pass_dropped", "four_updates", "gate_detached", "logits_bf16")


def one_step_cell(tiny):
    cell = run.resolve(BENCH_DIR, CELL)
    if tiny:
        import cpu_cell_ouro
        preset = cpu_cell_ouro.cpu_cell.PRESETS[cell["traffic_name"]]
        cell["config"]["model"].update(preset["model"])
        cell["traffic"].update(preset["traffic"])
    traffic = cell["traffic"]
    traffic["n_train"] = traffic["n_valid"] = traffic["minibatch"]
    traffic["check"] = dict(traffic.get("check", {}), train_epochs=1)
    cell["config"]["program"]["overrides"][
        "root.lm.decision.max_epochs"] = 1
    return cell


def group_of(path):
    """``['layers'][2]['ffn']['weights2']`` -> ``layers.2.ffn``."""
    parts = [p.strip("'") for p in path.strip("[]").split("][")]
    return ".".join(parts[:3]) if parts[0] == "layers" else parts[0]


base.group_of = group_of


# -- the twins: the reference's gradient through its seams --------------------


def twin_gradients(ref, tree, batch, model, live=None, detach_gate=False,
                   bf16_logits=False):
    """Mean-loss gradient tree (numpy, on the host) of the reference
    with: ``live`` = the one pass whose use of the shared parameters
    (layers, final norm, head, gate) adds to their gradient (None:
    every pass); ``detach_gate``: the exit weights are constants in
    the expected loss; ``bf16_logits``: every exit's logits are rounded
    to bf16 before the softmax. Each goes in through a seam of
    ``ref.sequence_gradients``."""
    import jax
    import jax.numpy as jnp
    tokens, labels, _ = ref._batch(batch, model)

    def exit_terms(shared, h, want, token_block):
        plain = ref.mm

        def rounded(a, b):
            return plain(a, b).astype(jnp.bfloat16).astype(jnp.float32)

        ref.mm = rounded
        try:
            return ref.exit_terms(shared, h, want, token_block)
        finally:
            ref.mm = plain

    def total(ce, gates, beta):
        if not detach_gate:
            return ref.expected_loss(ce, gates, beta)
        p = ref.exit_mass(gates)
        entropy = -(p * jnp.log(jnp.maximum(p, 1e-30))).sum(0)
        return ((jax.lax.stop_gradient(p) * ce).sum(0)
                - beta * entropy).sum()

    seams = {"counts": lambda t: live in (None, t)}
    if bf16_logits:
        seams["exit_fn"] = exit_terms
    if detach_gate:
        seams["total_fn"] = total
    on_device = jax.device_put(tree)
    summed = None
    for t, l in zip(tokens, labels):
        _, g = ref.sequence_gradients(on_device, t, l, model, **seams)
        summed = g if summed is None else jax.tree_util.tree_map(
            numpy.add, summed, g)
    ref._stages.cache_clear()       # the seams' closures die here
    return jax.tree_util.tree_map(lambda a: a / tokens.size, summed)


def fp8_scaled(a):
    """``a`` rounded to fp8 (e4m3) after scaling its largest magnitude
    to the format's 448, straight through for the gradient: the
    operand is the rounded one in both directions, the cotangent is
    not rounded on its way back through the cast."""
    import jax
    import jax.numpy as jnp
    scale = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / 448.0
    rounded = (a / scale).astype(jnp.float8_e4m3fn).astype(a.dtype) * scale
    return a + jax.lax.stop_gradient(rounded - a)


def rounded_loss(ref, tree, batch, model, rounding):
    import jax
    ref.round_operand = rounding
    jax.clear_caches()
    try:
        return ref.loss(tree, batch, model)
    finally:
        ref.round_operand = None
        jax.clear_caches()


def faulty_change(ref, tree, batch, cell, fault):
    """The parameters' change over one step that did ``fault``."""
    import jax
    model = cell["config"]["model"]
    lr = numpy.float32(cell["traffic"]["learning_rate"])
    moment = numpy.float32(model["gradient_moment"])
    steps = model["ut_steps"]
    if fault == "four_updates":
        # the solver after every visit: the gradients are those of the
        # step's one forward (the program's backward sees the weights
        # the forward saw), the momentum is applied four times
        velocity = jax.tree_util.tree_map(numpy.zeros_like, tree)
        moved = jax.tree_util.tree_map(numpy.zeros_like, tree)
        shared = ("layers", "out_norm", "gate", "head")
        for t in range(steps, 0, -1):
            g = twin_gradients(ref, tree, batch, model, live=t)
            for key in tree:
                if key not in shared and t != 1:
                    continue        # the embedding is visited once
                velocity[key] = jax.tree_util.tree_map(
                    lambda v, g: moment * v - lr * g, velocity[key],
                    g[key])
                moved[key] = jax.tree_util.tree_map(
                    numpy.add, moved[key], velocity[key])
        stepped = jax.tree_util.tree_map(numpy.add, tree, moved)
        return base.changes(tree, stepped)
    if fault == "pass_dropped":
        whole = twin_gradients(ref, tree, batch, model)
        first = twin_gradients(ref, tree, batch, model, live=1)
        g = dict(whole, layers=jax.tree_util.tree_map(
            numpy.subtract, whole["layers"], first["layers"]))
    else:
        switches = {"gate_detached": {"detach_gate": True},
                    "logits_bf16": {"bf16_logits": True}}[fault]
        g = twin_gradients(ref, tree, batch, model, **switches)
    stepped = jax.tree_util.tree_map(lambda w, g: w - lr * g, tree, g)
    return base.changes(tree, stepped)


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
    want, loss = base.reference_step(ref, tree, initial["train"], cell)
    result = {"seed": args.seed, "device": devices[0].device_kind,
              "train_loss": {"program": epoch["train"]["loss"],
                             "reference": loss},
              "program": base.distances(program, want)}
    print("program: %s" % json.dumps(result["program"]), flush=True)
    if args.controls:
        import jax.numpy as jnp
        roundings = (("reference_bf16_operands",
                      base.rounded_to(jnp.bfloat16)),
                     ("reference_fp8_operands", fp8_scaled))
        result["validation_loss"] = {
            "program": epoch["validation"]["loss"],
            "reference": ref.loss(tree, initial["valid"], model)}
        for name, rounding in roundings:
            result["validation_loss"][name] = rounded_loss(
                ref, tree, initial["valid"], model, rounding)
        print("validation_loss: %s" % json.dumps(
            result["validation_loss"]), flush=True)
        for name, rounding in roundings:
            got, _ = base.reference_step(
                ref, tree, initial["train"], cell, rounding)
            result[name] = base.distances(got, want)
            result["program_against_" + name] = \
                base.distances(program, got)["all"]
            print("%s: %s" % (name, json.dumps(result[name])),
                  flush=True)
    if args.faults:
        for fault in FAULTS:
            got = faulty_change(ref, tree, initial["train"][0], cell,
                                fault)
            result[fault] = base.distances(got, want)
            print("%s: %s" % (fault, json.dumps(result[fault])),
                  flush=True)
    line = json.dumps(result)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            f.write(line + "\n")
    print(line, flush=True)


if __name__ == "__main__":
    main()
