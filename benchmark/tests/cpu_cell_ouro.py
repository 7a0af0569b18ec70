"""``cpu_cell.py`` with the tiny preset of the Ouro cell.

``cpu_cell.py`` keeps its presets in a table keyed by traffic name, and
a PR that adds a cell may not edit it; this runner adds the new
traffic's preset to that table and hands over, as ``cpu_cell_lfm2.py``
does. (PERF.md section 7 asks the next ``benchmark`` PR to read presets
from files beside the runner.)

    JAX_PLATFORMS=cpu python3 benchmark/tests/cpu_cell_ouro.py \
        --workload ouro_2_6b_s8k_train [--trace 1] [--seconds 3]
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import cpu_cell     # noqa: E402

#: d 32, 2 heads of 16, FFN 48, two layers run three times, S 64; 64
#: sequences, not the cell's 2: a tiny epoch must last long enough that
#: the program's chunk policy settles inside a few seconds
TINY_OURO = {"dim": 32, "heads": 2, "kv_heads": 2, "head_dim": 16,
             "layers": ["plain_attention"] * 2, "dense_layers": 2,
             "ffn_hidden": 48, "ut_steps": 3, "vocab": 32,
             "attn_block": 16}
cpu_cell.PRESETS["ouro_s8k_train"] = {"model": TINY_OURO, "traffic": {
    "seq_len": 64, "minibatch": 1, "n_train": 64, "n_valid": 2,
    "max_period": 40, "learning_rate": 0.01}}

if __name__ == "__main__":
    cpu_cell.main()
