"""Operations and bytes of the Solar-Open2 decoder
(``configs/solar_open2_250b.json``), computed from its shapes: what a
perfect implementation NEEDS. Causal attention is counted over the
S(S+1)/2 visible pairs, the delta-rule recurrence as the model WRITES
it (token by token: whatever algorithm runs it is measured against the
same count), the embedding gather is no matrix multiplication,
recomputed operations and padded rows are not counted.

The routed experts are counted IN EXPECTATION UNDER UNIFORM ROUTING: of
a token's ``moe_top_k`` experts, ``held / moe_experts`` are on this
chip (0.2 of an expert, at 8 of 320 with 8 held); the shared expert is
met by every token. What the router really sent is in the program's
counters, and ``expert_matmul_cost`` takes the pairs as an argument.
"""

BF16 = 2
F32 = 4

ATTENTION = "gated_nope_attention"
DELTA = "delta_attention"


def held_experts(model):
    lo, hi = model.get("experts_held") or (0, model["moe_experts"])
    return hi - lo


def expert_params(model, hidden="moe_hidden"):
    """Matrix parameters of ONE expert: W1, W3 (d x f) and W2 (f x d)."""
    return 3 * model["dim"] * model[hidden]


def operator_params(model, kind):
    """Matrix parameters of a layer's operator (the taps, gains,
    ``A_log`` and biases are no matrices: see ``vector_params``)."""
    d = model["dim"]
    if kind == DELTA:       # q, k, v, o; two low-rank gates; beta
        wide = model["delta_heads"] * model["delta_head_dim"]
        rank = model["delta_gate_rank"]
        return 4 * d * wide + 2 * rank * (d + wide) \
            + d * model["delta_heads"]
    wide = model["heads"] * model["head_dim"]   # q, gate, o; k, v
    return 3 * d * wide + 2 * d * model["kv_heads"] * model["head_dim"]


def vector_params(model, kind):
    """What a layer's operator holds beside its matrices."""
    d = model["dim"]
    if kind != DELTA:
        return d                                        # pre-norm
    wide = model["delta_heads"] * model["delta_head_dim"]
    return 3 * wide * model["delta_conv_kernel"] + model["delta_heads"] \
        + 2 * wide + d + model["delta_head_dim"]


def ffn_params(model):
    """One expert layer as held: the shared expert, the router, the
    held experts, the pre-norm (the selection biases are a buffer)."""
    d = model["dim"]
    return expert_params(model, "moe_shared_hidden") \
        + d * model["moe_experts"] \
        + held_experts(model) * expert_params(model) + d


def parameters(model):
    """Every trained parameter of the configuration as cut."""
    d = model["dim"]
    return 2 * d * model["vocab"] + d + sum(
        operator_params(model, kind) + vector_params(model, kind)
        + ffn_params(model) for kind in model["layers"])


def matmul_params(model):
    """Parameters a token meets in a matrix multiplication on this
    chip: per layer its operator's, the shared expert, the router and
    the expected share of the routed experts; once the head."""
    d = model["dim"]
    share = model["moe_top_k"] * held_experts(model) / model["moe_experts"]
    return d * model["vocab"] + sum(
        operator_params(model, kind)
        + expert_params(model, "moe_shared_hidden")
        + d * model["moe_experts"] + share * expert_params(model)
        for kind in model["layers"])


def layers_of(model, kind):
    return sum(k == kind for k in model["layers"])


def attention_flops_per_sequence(model, seq, passes=3):
    """Score and context matmuls of every softmax-attention layer for
    one sequence: 2 FLOP x 2 matmuls x head_dim over the S(S+1)/2
    causal pairs of each QUERY head; the backward needs four such
    matmuls. ``passes``: 1 forward alone, 3 forward + backward."""
    wide = model["heads"] * model["head_dim"]
    return passes * 2.0 * layers_of(model, ATTENTION) * seq * (seq + 1) \
        * wide


def delta_flops_per_token(model):
    """The recurrence of ONE delta-rule layer, forward, as written:
    per head ``diag(exp(a)) S`` (dk dv), the read ``S^T k`` (2 dk dv),
    the rank-one update (2 dk dv) and ``S^T q`` (2 dk dv)."""
    dk = model["delta_head_dim"]
    return 7.0 * model["delta_heads"] * dk * dk


def train_flops_per_token(model, seq):
    """Forward + backward FLOPs one trained token needs: 6 per matmul
    parameter it meets (2 forward, 4 backward), its share of attention
    and three times the recurrences' forward."""
    return 6.0 * matmul_params(model) \
        + attention_flops_per_sequence(model, seq) / seq \
        + 3.0 * layers_of(model, DELTA) * delta_flops_per_token(model)


def train_flops_per_sample(model, traffic):
    """A sample is one sequence of ``traffic["seq_len"]`` tokens."""
    seq = traffic["seq_len"]
    return train_flops_per_token(model, seq) * seq


def expert_matmul_cost(model, pairs, backward=True):
    """(flops, bytes) the grouped products of ONE expert layer need for
    ``pairs`` token-expert pairs on its held experts — the routed
    experts alone, not the shared one: forward alone, or forward +
    backward. As ``costs/lfm2_moe.py``: 2 FLOP a pair and expert
    parameter forward, 4 more backward; the held experts' bf16 weights
    read once a pass, their float32 gradient written once, and the
    pairs' rows in and out of each product."""
    d, f = model["dim"], model["moe_hidden"]
    weights = held_experts(model) * expert_params(model)
    rows = pairs * (2 * d + 3 * f) * BF16
    if not backward:
        return 2.0 * expert_params(model) * pairs, weights * BF16 + rows
    return (6.0 * expert_params(model) * pairs,
            weights * (2 * BF16 + F32) + 3 * rows)


def attention_kernel_cost(model, traffic, backward=True):
    """(flops, bytes) the attention kernels need for ONE minibatch, the
    softmax-attention layers alone: forward alone (a validation
    minibatch) or forward + backward (an optimizer step). Bytes as for
    ``costs/lm.py``: four (B, S, heads x head_dim) bf16 tensors a layer
    forward and eight more backward — the kernels see K and V repeated
    to the query heads — plus the float32 row statistics."""
    seq, batch = traffic["seq_len"], traffic["minibatch"]
    passes, tensors, stats = (3, 12.0, 2.0) if backward else (1, 4.0, 1.0)
    flops = batch * attention_flops_per_sequence(model, seq, passes)
    tensor = batch * seq * model["heads"] * model["head_dim"] * BF16
    rows = batch * model["heads"] * seq * F32
    return flops, layers_of(model, ATTENTION) * (tensors * tensor
                                                 + stats * rows)


def delta_core_cost(model, tokens, backward=True):
    """(flops, bytes) the recurrence of ONE delta-rule layer needs for
    ``tokens`` tokens: forward alone, or forward + backward (twice the
    forward's operations more). Bytes, the least traffic in the compute
    type: ``q, k, a`` (dk a head), ``v`` (dv) and ``b`` (1) read and
    ``o`` (dv) written forward; backward all five and ``o``'s cotangent
    read, five cotangents written. The state never leaves the chip's
    fast memory in a perfect implementation."""
    h, dk = model["delta_heads"], model["delta_head_dim"]
    forward = delta_flops_per_token(model) * tokens
    inputs = h * (4 * dk + 1)
    if not backward:
        return forward, tokens * (inputs + h * dk) * BF16
    return 3.0 * forward, tokens * (3 * inputs + 2 * h * dk) * BF16
