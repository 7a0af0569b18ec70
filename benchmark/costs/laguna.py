"""Operations and bytes of the Laguna decoder
(``configs/laguna_s_2_1.json``), computed from its shapes: what a
perfect implementation NEEDS. A full-attention layer is counted over
the S(S+1)/2 visible pairs of its query heads, a sliding layer over the
pairs INSIDE ITS BAND (query t sees min(t + 1, window) keys: 4,063,488
a head at S = 8192, window 512, where the triangle holds 33,558,528) —
a kernel that visits masked tiles is charged their time and not
credited their work. The embedding gather is no matrix multiplication,
recomputed operations and padded rows are not counted.

The routed experts are counted IN EXPECTATION UNDER UNIFORM ROUTING: of
a token's ``moe_top_k`` experts, ``held / moe_experts`` are on this
chip (0.3125 of an expert, at 10 of 256 with 8 held); the shared expert
and the leading dense layer are met by every token. What the router
really sent is in the program's counters, and ``expert_matmul_cost``
takes the pairs as an argument.
"""

BF16 = 2
F32 = 4


def held_experts(model):
    lo, hi = model.get("experts_held") or (0, model["moe_experts"])
    return hi - lo


def expert_params(model, hidden="moe_hidden"):
    """Matrix parameters of ONE expert: W1, W3 (d x f) and W2 (f x d)."""
    return 3 * model["dim"] * model[hidden]


def operator_params(model, kind):
    """Matrix parameters of a layer's operator: W_q and W_o at its own
    head count, W_k and W_v at the K/V heads, the per-head gate."""
    d, dh = model["dim"], model["head_dim"]
    heads = model["operators"][kind]["heads"]
    return 2 * d * heads * dh + 2 * d * model["kv_heads"] * dh \
        + d * heads


def ffn_params(model, index):
    """Layer ``index``'s feed-forward as held, with its pre-norm: the
    dense SwiGLU, or the shared expert, the router and the held experts
    (the selection biases are a buffer)."""
    d = model["dim"]
    if index < model["dense_layers"]:
        return 3 * d * model["ffn_hidden"] + d
    return expert_params(model, "moe_shared_hidden") \
        + d * model["moe_experts"] \
        + held_experts(model) * expert_params(model) + d


def parameters(model):
    """Every trained parameter of the configuration as cut."""
    d = model["dim"]
    return 2 * d * model["vocab"] + d + sum(
        operator_params(model, kind) + d + ffn_params(model, index)
        for index, kind in enumerate(model["layers"]))


def matmul_params(model):
    """Parameters a token meets in a matrix multiplication on this
    chip: per layer its operator's and either the dense SwiGLU or the
    shared expert, the router and the expected share of the routed
    experts; once the head."""
    d = model["dim"]
    share = model["moe_top_k"] * held_experts(model) / model["moe_experts"]
    total = d * model["vocab"]
    for index, kind in enumerate(model["layers"]):
        total += operator_params(model, kind)
        if index < model["dense_layers"]:
            total += 3 * d * model["ffn_hidden"]
        else:
            total += expert_params(model, "moe_shared_hidden") \
                + d * model["moe_experts"] + share * expert_params(model)
    return total


def visible_pairs(seq, window=None):
    """Query-key pairs a causal row of ``seq`` tokens attends: query t
    sees min(t + 1, window) keys."""
    if window is None or window >= seq:
        return seq * (seq + 1) // 2
    return window * (window + 1) // 2 + (seq - window) * window


def pair_flops(model, backward):
    """Operations ONE query-key pair of ONE head costs in the attention
    proper: 2 FLOP x 2 matmuls x head_dim forward (scores, context),
    four more matmuls backward (``costs/lfm2_moe.py``'s count: S(S+1)
    x wide x 2 forward is 4 head_dim a pair)."""
    return (12.0 if backward else 4.0) * model["head_dim"]


def attention_flops_per_sequence(model, seq, passes=3, windowed=None):
    """Score and context matmuls of the attention layers for one
    sequence, over each layer's visible pairs at ITS head count.
    ``passes``: 1 forward alone, 3 forward + backward. ``windowed``:
    None every layer, True the sliding ones alone, False the others."""
    total = 0.0
    for kind in model["layers"]:
        own = model["operators"][kind]
        if windowed is not None and bool(own.get("window")) != windowed:
            continue
        total += own["heads"] * visible_pairs(seq, own.get("window"))
    return passes * pair_flops(model, backward=False) * total


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
    ``pairs`` token-expert pairs on its held experts — the routed
    experts alone, not the shared one. As ``costs/lfm2_moe.py``: 2 FLOP
    a pair and expert parameter forward, 4 more backward; the held
    experts' bf16 weights read once a pass, their float32 gradient
    written once, and the pairs' rows in and out of each product."""
    d, f = model["dim"], model["moe_hidden"]
    weights = held_experts(model) * expert_params(model)
    rows = pairs * (2 * d + 3 * f) * BF16
    if not backward:
        return 2.0 * expert_params(model) * pairs, weights * BF16 + rows
    return (6.0 * expert_params(model) * pairs,
            weights * (2 * BF16 + F32) + 3 * rows)


def kernel_bytes(model, tokens, heads, kv_heads, backward):
    """The least traffic of one layer's attention proper over
    ``tokens`` tokens, bf16: q and out at ``heads``, k and v at
    ``kv_heads`` forward (4 tensors at equal heads); backward those and
    out's cotangent read and three cotangents written; the float32 row
    statistics written forward and read backward."""
    dh = model["head_dim"]
    q, kv = tokens * heads * dh * BF16, tokens * kv_heads * dh * BF16
    rows = tokens * heads * F32
    if not backward:
        return 2 * q + 2 * kv + rows
    return 6 * q + 6 * kv + 2 * rows


def attention_kernel_cost(model, traffic, backward=True):
    """(flops, bytes) the attention kernels need for ONE minibatch,
    every attention layer — the full layers' causal triangle at their
    heads PLUS the sliding layers' band at theirs (all the Mosaic
    kernels under ``veles.core`` are timed against it): forward alone
    (a validation minibatch) or forward + backward (an optimizer step).
    Bytes as for ``costs/lfm2_moe.py``: the kernels see K and V
    repeated to the query heads."""
    seq, batch = traffic["seq_len"], traffic["minibatch"]
    flops = batch * attention_flops_per_sequence(
        model, seq, 3 if backward else 1)
    nbytes = sum(
        kernel_bytes(model, batch * seq, own["heads"], own["heads"],
                     backward)
        for own in (model["operators"][k] for k in model["layers"]))
    return flops, nbytes


def window_kernel_cost(model, pairs, tokens, heads, backward=True):
    """(flops, bytes) the BAND of one sliding layer needs: ``pairs``
    query-key pairs (all heads; the program's counter) at the
    operations a pair costs in ``attention_kernel_cost``, and the bytes
    of q, k, v, out and their cotangents over ``tokens`` tokens at the
    K/V heads the MODEL has (a kernel that reads K and V repeated is
    held to the same count)."""
    return (pair_flops(model, backward) * pairs,
            kernel_bytes(model, tokens, heads, model["kv_heads"],
                         backward))
