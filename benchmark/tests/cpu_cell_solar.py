"""``cpu_cell.py`` with the tiny preset of the Solar-Open2 cell.

``cpu_cell.py`` keeps its presets in a table keyed by traffic name, and
a PR that adds a cell may not edit it; this runner adds the new
traffic's preset to that table and hands over, as ``cpu_cell_ouro.py``
does.

    JAX_PLATFORMS=cpu python3 benchmark/tests/cpu_cell_solar.py \
        --workload solar_open2_250b_s4k_train [--trace 1] [--seconds 3]
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import cpu_cell     # noqa: E402

#: d 32; 4 query / 2 K/V heads of 8; 4 delta-rule heads of 8 with gates
#: of rank 4; 8 experts top-2 of width 16, 4 held, a shared one of 16;
#: one period, S 128 (two chunks of the recurrence); 24 sequences, not
#: the cell's 8: a tiny epoch must last long enough that the program's
#: chunk policy settles inside a few seconds. The learning rate is a
#: fiftieth of the cell's: at this width and 0.01 two float32 orders of
#: the SAME sums part by 1e-4 after five steps and 5e-2 after eight
#: (the reference's walk against its own jax.grad: the delta-rule
#: layers' training amplifies rounding tenfold a step), which is no
#: fault of either side and fails any tolerance
TINY_SOLAR = {"dim": 32, "heads": 4, "kv_heads": 2, "head_dim": 8,
              "delta_heads": 4, "delta_head_dim": 8, "delta_gate_rank": 4,
              "moe_hidden": 16, "moe_shared_hidden": 16, "moe_experts": 8,
              "moe_top_k": 2, "experts_held": [0, 4], "vocab": 32,
              "attn_block": 16}
cpu_cell.PRESETS["solar_s4k_train"] = {"model": TINY_SOLAR, "traffic": {
    "seq_len": 128, "minibatch": 1, "n_train": 24, "n_valid": 1,
    "max_period": 40, "learning_rate": 0.0002}}

if __name__ == "__main__":
    cpu_cell.main()
