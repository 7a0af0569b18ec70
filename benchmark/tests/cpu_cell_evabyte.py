"""``cpu_cell.py`` with the tiny preset of the EVA byte LM cell.

``cpu_cell.py`` keeps its presets in a table keyed by traffic name, and
a PR that adds a cell may not edit it; this runner adds the new
traffic's preset to that table and hands over, as ``cpu_cell_laguna.py``
does.

    JAX_PLATFORMS=cpu python3 benchmark/tests/cpu_cell_evabyte.py \
        --workload evabyte_s16k_train [--trace 1] [--seconds 3]
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import cpu_cell     # noqa: E402

#: d 64; 4 heads of 16; window 32 and chunks of 4 over S 128 (4
#: windows, 32 summaries, prefixes of 0 to 24), kernel tiles of 16 (the
#: Pallas interpreter); a SwiGLU of 96; 3 prediction heads over 32 ids;
#: two layers; 24 sequences, not the cell's 4: a tiny epoch must last
#: long enough that the program's chunk policy settles inside a few
#: seconds (and a step long enough that a dispatch's spans do not wrap
#: the flight recorder). The check keeps the training comparison the
#: cell leaves out (its limit waits on the chip's readings): here the
#: program computes in float32 and agrees with the reference to float32
#: rounding (at most 3e-7 after an epoch), where bf16 operands read
#: 1e-4 and fp8 2e-3 (``chip_grads_evabyte.py --tiny 1 --epoch 1``)
TINY_EVA = {"dim": 64, "heads": 4, "kv_heads": 4, "head_dim": 16,
            "layers": ["eva_attention"] * 2, "dense_layers": 2,
            "ffn_hidden": 96, "eva_window": 32, "eva_chunk": 4,
            "vocab": 32, "pred_heads": 3}
cpu_cell.PRESETS["evabyte_s16k_train"] = {"model": TINY_EVA, "traffic": {
    "seq_len": 128, "minibatch": 1, "n_train": 24, "n_valid": 1,
    "max_period": 80, "learning_rate": 0.01,
    "check": {"forward_tolerance": 3e-5, "train_epochs": 1,
              "train_tolerance": 3e-5, "loss_falls": True}}}

if __name__ == "__main__":
    cpu_cell.main()
