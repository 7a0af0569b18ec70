"""Operations and bytes of the LFM2-MoE decoder
(``configs/lfm2_24b_a2b.json``), computed from its shapes: what a
perfect implementation NEEDS. Causal attention is counted over the
S(S+1)/2 visible pairs, the embedding gather is no matrix
multiplication, recomputed operations and padded rows are not counted.

The expert layers are counted IN EXPECTATION UNDER UNIFORM ROUTING: of
a token's ``moe_top_k`` experts, ``held / moe_experts`` are on this
chip, so a token meets ``moe_top_k * held / moe_experts`` experts'
parameters here (one expert's, at 4 of 64 with 16 held). What the
router really sent is in the program's counters
(``veles_moe_pairs_total``), and ``expert_matmul_cost`` takes the pairs
as an argument.
"""

BF16 = 2
F32 = 4


def held_experts(model):
    lo, hi = model.get("experts_held") or (0, model["moe_experts"])
    return hi - lo


def expert_params(model):
    """Matrix parameters of ONE expert: W1, W3 (d x f) and W2 (f x d)."""
    return 3 * model["dim"] * model["moe_hidden"]


def operator_params(model, kind):
    d = model["dim"]
    if kind == "conv":                  # W_in d x 3d, W_out d x d
        return 4 * d * d
    wide = (model["heads"] + 2 * model["kv_heads"]) * model["head_dim"]
    return d * wide + model["heads"] * model["head_dim"] * d


def matmul_params(model):
    """Parameters a token meets in a matrix multiplication on this
    chip: per layer its operator's projections and either the dense
    SwiGLU FFN (3 d f) or the router (d x E) plus the expected share of
    the experts; once the vocabulary head (d x V)."""
    d = model["dim"]
    share = model["moe_top_k"] * held_experts(model) / model["moe_experts"]
    total = d * model["vocab"]
    for index, kind in enumerate(model["layers"]):
        total += operator_params(model, kind)
        if index < model["dense_layers"]:
            total += 3 * d * model["ffn_hidden"]
        else:
            total += d * model["moe_experts"] \
                + share * expert_params(model)
    return total


def attention_layers(model):
    return sum(kind == "full_attention" for kind in model["layers"])


def attention_flops_per_sequence(model, seq, passes=3):
    """Score and context matmuls of every attention layer for one
    sequence: 2 FLOP x 2 matmuls x head_dim over the S(S+1)/2 causal
    pairs of each of the ``heads`` QUERY heads (K/V heads being fewer
    saves bytes, not operations); the backward needs four such
    matmuls. ``passes``: 1 forward alone, 3 forward + backward."""
    wide = model["heads"] * model["head_dim"]
    return passes * 2.0 * attention_layers(model) * seq * (seq + 1) * wide


def train_flops_per_token(model, seq):
    """Forward + backward FLOPs one trained token needs: 6 per matmul
    parameter it meets (2 forward, 4 backward) plus its share of
    attention."""
    return 6.0 * matmul_params(model) \
        + attention_flops_per_sequence(model, seq) / seq


def train_flops_per_sample(model, traffic):
    """A sample is one sequence of ``traffic["seq_len"]`` tokens."""
    seq = traffic["seq_len"]
    return train_flops_per_token(model, seq) * seq


def expert_matmul_cost(model, pairs, backward=True):
    """(flops, bytes) the grouped products of ONE expert layer need for
    ``pairs`` token-expert pairs on its held experts: forward alone, or
    forward + backward (a training step).

    FLOPs: 2 per pair and expert parameter forward, 4 more backward
    (6 x 3 x d x f x pairs in all). Bytes, the least HBM traffic in the
    compute type: the held experts' bf16 weights read once a pass
    (forward, and the backward's data pass), their float32 gradient
    written once; and the pairs' rows in and out of each product — a
    forward moves d + 2f (up-projections) and f + d (down-projection)
    values a pair, each of the backward's two passes as many."""
    d, f = model["dim"], model["moe_hidden"]
    weights = held_experts(model) * expert_params(model)
    rows = pairs * (2 * d + 3 * f) * BF16
    if not backward:
        return 2.0 * expert_params(model) * pairs, weights * BF16 + rows
    return (6.0 * expert_params(model) * pairs,
            weights * (2 * BF16 + F32) + 3 * rows)


def attention_kernel_cost(model, traffic, backward=True):
    """(flops, bytes) the attention kernels need for ONE minibatch,
    every attention layer: forward alone (a validation minibatch) or
    forward + backward (an optimizer step). Bytes as for
    ``costs/lm.py``, the least of a flash formulation in bf16: four
    (B, S, heads x head_dim) tensors a layer forward (q, k, v read,
    out written) and eight more backward (q, k, v, out, dout read,
    dq, dk, dv written) — the kernels see K and V repeated to the
    query heads — plus the float32 row statistics written forward and
    read backward."""
    seq, batch = traffic["seq_len"], traffic["minibatch"]
    passes, tensors, stats = (3, 12.0, 2.0) if backward else (1, 4.0, 1.0)
    flops = batch * attention_flops_per_sequence(model, seq, passes)
    tensor = batch * seq * model["heads"] * model["head_dim"] * BF16
    rows = batch * model["heads"] * seq * F32
    return flops, attention_layers(model) * (tensors * tensor
                                             + stats * rows)
