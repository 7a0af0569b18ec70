"""``cpu_cell.py`` with the tiny preset of the LFM2 cell.

``cpu_cell.py`` keeps its presets in a table keyed by traffic name, and
a PR that adds a cell may not edit it; this runner adds the new
traffic's preset to that table and hands over. (PERF.md section 7 asks
the next ``benchmark`` PR to read presets from files beside the runner.)

    JAX_PLATFORMS=cpu python3 benchmark/tests/cpu_cell_lfm2.py \
        --workload lfm2_24b_a2b_s8k_train [--trace 1] [--seconds 3]
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import cpu_cell     # noqa: E402

#: d 64, 4 query / 2 K/V heads of 16, 8 experts (2 held) top-2 of width
#: 32, dense FFN 96, the configuration's five layers, S 64
TINY_LFM2 = {"dim": 64, "heads": 4, "kv_heads": 2, "head_dim": 16,
             "ffn_hidden": 96, "moe_hidden": 32, "moe_experts": 8,
             "moe_top_k": 2, "experts_held": [0, 2], "vocab": 32,
             "attn_block": 16}
cpu_cell.PRESETS["lfm2_s8k_train"] = {"model": TINY_LFM2, "traffic": {
    "seq_len": 64, "minibatch": 2, "n_train": 16, "n_valid": 2,
    "max_period": 40, "learning_rate": 0.05}}

if __name__ == "__main__":
    cpu_cell.main()
