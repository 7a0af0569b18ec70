"""``cpu_cell.py`` with the tiny preset of the Laguna cell.

``cpu_cell.py`` keeps its presets in a table keyed by traffic name, and
a PR that adds a cell may not edit it; this runner adds the new
traffic's preset to that table and hands over, as ``cpu_cell_solar.py``
does.

    JAX_PLATFORMS=cpu python3 benchmark/tests/cpu_cell_laguna.py \
        --workload laguna_s_2_1_s8k_train [--trace 1] [--seconds 3]
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import cpu_cell     # noqa: E402

#: d 64; 2 K/V heads of 16 under 4 query heads (groups of 2) in the
#: full layers, half of each head rotated under YaRN over 32 original
#: positions, and 6 query heads (groups of 3) in the sliding ones,
#: window 40 of S 128 (no multiple of the scan's block of 16); a dense
#: SwiGLU of 96 in layer 0, then 8 experts top-3 of width 32, 4 held, a
#: shared one of 32; the pattern dense + S, S, S, F; 24 sequences, not
#: the cell's 4: a tiny epoch must last long enough that the program's
#: chunk policy settles inside a few seconds (and a step long enough
#: that a dispatch's spans do not wrap the flight recorder)
TINY_OPERATORS = {
    "full_attention": {
        "heads": 4, "rope_theta": 500000.0, "rotary_dim": 8,
        "rope_scaling": {
            "rope_type": "yarn", "factor": 8.0,
            "original_max_position_embeddings": 32, "beta_slow": 1,
            "beta_fast": 4, "attention_factor": 1.2079441541679836},
        "gate": "head", "qk_norm": False},
    "sliding_attention": {"heads": 6, "rope_theta": 10000.0, "window": 40,
                          "gate": "head", "qk_norm": False}}
TINY_LAGUNA = {"dim": 64, "kv_heads": 2, "head_dim": 16,
               "operators": TINY_OPERATORS, "ffn_hidden": 96,
               "moe_hidden": 32, "moe_shared_hidden": 32,
               "moe_experts": 8, "moe_top_k": 3, "experts_held": [0, 4],
               "vocab": 32, "attn_block": 16}
cpu_cell.PRESETS["laguna_s8k_train"] = {"model": TINY_LAGUNA, "traffic": {
    "seq_len": 128, "minibatch": 1, "n_train": 24, "n_valid": 1,
    "max_period": 40, "learning_rate": 0.01}}

if __name__ == "__main__":
    cpu_cell.main()
