"""Run one cell on the CPU at a tiny preset: the rehearsal of control
flow that costs no chip time. Not a measurement — the last line's
``device`` says ``cpu`` and no number of it is a device metric.

    JAX_PLATFORMS=cpu python3 benchmark/tests/cpu_cell.py \
        --workload lm110m_s512_train [--trace 1] [--seconds 3]

The presets live here, not under ``configs/``: a toy size under a real
configuration's name must never be one flag away from the command.
"""

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.dirname(HERE)

TINY_LM = {"dim": 32, "heads": 2, "head_dim": 16, "layers": 2,
           "ffn_hidden": 64, "vocab": 32, "attn_block": 16}
#: {traffic name: overrides of the configuration's model and the traffic}
PRESETS = {
    "s8k_train": {"model": TINY_LM, "traffic": {
        "seq_len": 64, "minibatch": 4, "n_train": 16, "n_valid": 4}},
    "s512_train": {"model": TINY_LM, "traffic": {
        "seq_len": 32, "minibatch": 8, "n_train": 32, "n_valid": 8}},
    "s512_dp4": {"model": TINY_LM, "traffic": {
        "seq_len": 32, "minibatch": 8, "n_train": 32, "n_valid": 8}},
    # the program's AlexNet has no width to turn; the batch shrinks,
    # and with it the learning rate (0.01 diverges at batch 2)
    # (four images under dropout: the loss wanders, so it is not asked
    # to fall)
    "b128_train": {"model": {"learning_rate": 1e-4}, "traffic": {
        "minibatch": 2, "n_train": 4, "n_valid": 2,
        "check": {"forward_tolerance": 1e-4, "loss_falls": False}}},
}


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=1)
    p.add_argument("--seconds", type=float, default=2.0)
    p.add_argument("--trace", type=int, default=0)
    p.add_argument("--bench-dir", default=BENCH_DIR)
    args = p.parse_args()
    checkout = os.path.dirname(os.path.abspath(args.bench_dir))
    sys.path.insert(0, checkout)
    from benchmark import run
    cell = run.resolve(args.bench_dir, args.workload)
    if cell["chips"] > 1:
        from veles import backends
        backends.force_virtual_cpu_devices(cell["chips"])
    result = run.run_cell(args.bench_dir, args.workload, args.seed,
                          args.seconds, args.trace, platform="cpu",
                          overrides=PRESETS.get(cell["traffic_name"], {}))
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
