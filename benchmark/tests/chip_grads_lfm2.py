"""The parameters' change over ONE optimizer step of the LFM2 cell,
program against reference, at the timed sizes on the chip.

    python3 benchmark/tests/chip_grads_lfm2.py --seed <n> \
        [--controls 1] [--plant expert_grads|kv_group] [--out <file>]

The cell's own ``correct`` compares losses, and on this traffic a loss
hardly feels a gradient (PERF.md section 6, PR 28): sixteen random
sequences, each seen once an epoch, teach nothing about the next one.
This run compares what a step DID. The program trains the cell's
configuration for one epoch of one step (``n_train`` = ``minibatch``,
everything else the cell's: S, batch, widths, kernels, solver) through
``python -m veles``' entry point; the reference takes the same weights
and the same minibatch through ``jax.grad`` and momentum SGD in
float32. Momentum starts at zero, so a parameter's change over the step
is minus the learning rate times its gradient, and

    distance = |change(program) - change(reference)| / |change(reference)|

(Euclidean norms over a unit's parameters, and over all of them) is the
relative error of the gradient as the solver applied it. 0 is
agreement; a state left unchanged reads 1.

``--controls 1`` also reads, against the same float32 change, what the
measure gives for the reference itself computed (a) with bf16 matmul
operands, the precision the configuration states, (b) with fp8 (e4m3)
operands, the nearest below it, and (c) on the first sequence of the
minibatch alone (half the batch left out). ``--plant`` puts a fault
into the PROGRAM before it is built: ``expert_grads`` hands the solver
zeros for the expert weights' gradients, ``kv_group`` makes the
backward of the K/V repeat take the first query head of each group
instead of the group's sum.

The last line of standard output is one JSON object. Not a cell: its
numbers go to PERF.md by hand, and ``BENCHMARK.json`` does not list it.
``--tiny 1 --platform cpu`` rehearses the control flow at the CPU
preset of ``cpu_cell_lfm2.py``.
"""

import time

T0 = time.perf_counter()

import argparse                 # noqa: E402
import json                     # noqa: E402
import os                       # noqa: E402
import sys                      # noqa: E402
import threading                # noqa: E402

import numpy                    # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.dirname(HERE)
CHECKOUT = os.path.dirname(BENCH_DIR)
sys.path[:0] = [CHECKOUT, HERE]

from benchmark import harness, run              # noqa: E402
from benchmark.drivers import train             # noqa: E402

CELL = "lfm2_24b_a2b_s8k_train"


def one_step_cell(tiny):
    cell = run.resolve(BENCH_DIR, CELL)
    if tiny:
        import cpu_cell_lfm2
        preset = cpu_cell_lfm2.cpu_cell.PRESETS[cell["traffic_name"]]
        cell["config"]["model"].update(preset["model"])
        cell["traffic"].update(preset["traffic"])
    traffic = cell["traffic"]
    traffic["n_train"] = traffic["n_valid"] = traffic["minibatch"]
    traffic["check"] = dict(traffic.get("check", {}), train_epochs=1)
    cell["config"]["program"]["overrides"][
        "root.lm.decision.max_epochs"] = 1
    return cell


def plant(name):
    """Put the fault ``name`` into the program's code."""
    if name == "expert_grads":
        from veles.znicz_tpu.ops.expert_ffn import GDExpertFFN
        sound = GDExpertFFN.apply_grads

        def apply_grads(self, ctx, grads):
            import jax.numpy as jnp
            sound(self, ctx, dict(
                grads, weights13=jnp.zeros_like(grads["weights13"]),
                weights2=jnp.zeros_like(grads["weights2"])))

        GDExpertFFN.apply_grads = apply_grads
    elif name == "kv_group":
        import jax
        from veles.znicz_tpu.ops import gqa_attention

        def repeat_heads(t, group):
            @jax.custom_vjp
            def repeat(t):
                return jax.numpy.repeat(t, group, axis=1)

            repeat.defvjp(lambda t: (repeat(t), None),
                          lambda _, g: (g[:, ::group],))
            return repeat(t)

        gqa_attention.repeat_heads = repeat_heads
    elif name:
        raise SystemExit("no fault named %r" % name)


def program_step(cell, seed, platform):
    """-> (what the watcher captured before the step, the units'
    parameters after it)."""
    from veles.__main__ import Main
    argv = train.build_argv(cell, seed, platform)
    print("+ python -m veles %s" % " ".join(argv), flush=True)
    main = Main(argv)
    watcher = train.Watcher(main, cell, 0, False, T0, None)
    failure = []

    def capture():
        try:
            watcher.wait_started()
            watcher.capture_initial()
        except BaseException as exc:
            failure.append(exc)

    thread = threading.Thread(target=capture, daemon=True)
    thread.start()
    try:
        main.run()
    finally:
        watcher.run_over.set()
        thread.join()
    if failure:
        raise failure[0]
    wf = main.workflow
    if len(wf.decision.history) != 1:
        raise SystemExit("%d epochs ran, not one"
                         % len(wf.decision.history))
    after = [(type(u).MAPPING, u.export_params()) for u in wf.forwards]
    return watcher.initial, after, wf.decision.history[0]


def changes(before, after):
    """{path: after - before} over the reference's parameter tree."""
    import jax
    flat = jax.tree_util.tree_leaves_with_path
    name = jax.tree_util.keystr
    return {name(path): numpy.asarray(b, numpy.float32) - a
            for (path, a), (_, b) in zip(flat(before), flat(after))}


def square(x):
    x = x.reshape(-1).astype(numpy.float64)
    return float(x @ x)


def group_of(path):
    """``['layers'][2]['ffn']['weights13']`` -> ``layers.2.ffn``."""
    parts = [p.strip("'") for p in path.strip("[]").split("][")]
    return ".".join(parts[:3]) if parts[0] == "layers" else parts[0]


def distances(got, want):
    """{group: |got - want| / |want|}, and ``all``; parameters whose
    reference change is zero (the selection biases) are left out."""
    sums = {}
    for path, w in want.items():
        if not w.any():
            continue
        d, n = square(got[path] - w), square(w)
        for key in (group_of(path), "all"):
            a, b = sums.get(key, (0.0, 0.0))
            sums[key] = (a + d, b + n)
    return {k: (d / n) ** 0.5 for k, (d, n) in sums.items()}


def rounded_to(dtype):
    def round_operand(a):
        return a.astype(dtype).astype(a.dtype)
    return round_operand


def reference_step(ref, tree, batches, cell, rounding=None):
    import jax
    model, traffic = cell["config"]["model"], cell["traffic"]
    ref.round_operand = rounding
    jax.clear_caches()
    try:
        stepped, losses = ref.train(tree, batches, model,
                                    traffic["learning_rate"],
                                    model["gradient_moment"])
    finally:
        ref.round_operand = None
    return changes(tree, stepped), losses[0]


def main():
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--controls", type=int, default=0)
    p.add_argument("--plant", default="")
    p.add_argument("--platform", default="tpu")
    p.add_argument("--tiny", type=int, default=0)
    p.add_argument("--out", default="")
    args = p.parse_args()
    os.chdir(CHECKOUT)
    cell = one_step_cell(args.tiny)
    devices = harness.require_devices(args.platform, cell["chips"])
    plant(args.plant)
    initial, after, epoch = program_step(cell, args.seed, args.platform)
    model = cell["config"]["model"]
    ref = harness.load_module(BENCH_DIR, "reference",
                              cell["config"]["reference"])
    tree = ref.from_program(initial["units"], model)
    program = changes(tree, ref.from_program(after, model))
    del after
    want, loss = reference_step(ref, tree, initial["train"], cell)
    result = {"seed": args.seed, "plant": args.plant or None,
              "device": devices[0].device_kind,
              "train_loss": {"program": epoch["train"]["loss"],
                             "reference": loss},
              "program": distances(program, want)}
    print("program: %s" % json.dumps(result["program"]), flush=True)
    if args.controls:
        import jax.numpy as jnp
        tokens, labels = initial["train"][0]
        controls = {
            "reference_bf16_operands": (initial["train"],
                                        rounded_to(jnp.bfloat16)),
            "reference_fp8_operands": (initial["train"],
                                       rounded_to(jnp.float8_e4m3fn)),
            "reference_half_the_batch": ([(tokens[:1], labels[:1])],
                                         None)}
        for name, (batches, rounding) in controls.items():
            got, _ = reference_step(ref, tree, batches, cell, rounding)
            result[name] = distances(got, want)
            # ... and the program's change against this one
            result["program_against_" + name] = \
                distances(program, got)["all"]
            print("%s: %s" % (name, json.dumps(result[name])),
                  flush=True)
    line = json.dumps(result)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            f.write(line + "\n")
    print(line, flush=True)


if __name__ == "__main__":
    main()
