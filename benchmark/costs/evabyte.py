"""Operations and bytes of the EVA byte LM (``configs/evabyte.json``),
computed from its shapes: what a perfect implementation NEEDS. The EVA
core is counted over the pairs its definition has, per head and
sequence: the local part over each window's causal triangle, S / W x
W (W + 1) / 2 query-key pairs (16,785,408 at S = 16384, W = 2048), the
remote part over every query's summaries of the earlier windows,
sum_w w W x W / C query-summary pairs (7,340,032 at C = 16) — a kernel
that visits a tile the definition only cuts is charged its time and not
credited its masked pairs. The summaries are counted as their scores
and sums (4 dh a key and pooling vector, two vectors), the embedding
gather as no matrix multiplication; recomputed operations and padded
rows are not counted.
"""

BF16 = 2
F32 = 4


def attention_params(model):
    """W_q, W_k, W_v and W_o of a layer."""
    d, h, dh = model["dim"], model["heads"], model["head_dim"]
    return 2 * d * h * dh + 2 * d * model["kv_heads"] * dh


def ffn_params(model):
    """The SwiGLU's W1, W3 (d x f) and W2 (f x d)."""
    return 3 * model["dim"] * model["ffn_hidden"]


def layer_params(model):
    """A layer: its matrices, two gains and the pooling vectors mu and
    phi (heads x head_dim each)."""
    return attention_params(model) + ffn_params(model) \
        + 2 * model["dim"] + 2 * model["heads"] * model["head_dim"]


def head_params(model):
    """The ``pred_heads`` untied heads, one product d x heads V."""
    return model["dim"] * model["pred_heads"] * model["vocab"]


def parameters(model, layers=None):
    """Every trained parameter of the configuration as cut (``layers``:
    another depth, the uncut count at the published one)."""
    depth = len(model["layers"]) if layers is None else layers
    return depth * layer_params(model) + model["vocab"] * model["dim"] \
        + head_params(model) + model["dim"]


def local_pairs(model, seq):
    """Query-key pairs of the local part, one head and sequence."""
    window = min(model["eva_window"], seq)
    return seq // window * window * (window + 1) // 2


def remote_pairs(model, seq):
    """Query-summary pairs of the remote part, one head and sequence:
    a query of window w sees the w W / C summaries before it."""
    window = min(model["eva_window"], seq)
    spans = seq // window
    return window * (window // model["eva_chunk"]) \
        * spans * (spans - 1) // 2


def pair_flops(model, backward):
    """Operations ONE pair of ONE head costs in the attention proper:
    2 FLOP x 2 matmuls x head_dim forward (scores, context), four more
    matmuls backward (``costs/laguna.py``'s count)."""
    return (12.0 if backward else 4.0) * model["head_dim"]


def summary_flops(model, seq, backward):
    """The pooling of one sequence, every head: a key's score against
    mu and phi and its weighted sums into k^ and v^, 2 FLOP a value
    each (8 dh a key forward), twice that again backward."""
    flops = 8.0 * model["head_dim"] * seq * model["heads"]
    return 3.0 * flops if backward else flops


def core_flops(model, seq, backward=True, part=None):
    """The EVA core of ONE layer over one sequence, every head: the
    local and the remote pairs (``part`` "local" or "remote" alone),
    forward or forward + backward."""
    pairs = {"local": local_pairs(model, seq),
             "remote": remote_pairs(model, seq)}
    counted = pairs.values() if part is None else [pairs[part]]
    return pair_flops(model, backward) * model["heads"] * sum(counted)


def core_bytes(model, tokens, backward):
    """The least HBM traffic of ONE layer's local and remote kernels
    over ``tokens`` tokens, bf16: forward q, k, v read and out written
    at every head, the summaries k^ and v^ read, the float32 row
    statistics (lse of each part) written; backward q, k, v, out, dout
    and the summaries read, dq, dk, dv and the summaries' cotangents
    written, lse and delta read."""
    h, dh = model["heads"], model["head_dim"]
    row = tokens * h * dh * BF16
    summary = row // model["eva_chunk"]
    stats = tokens * h * F32
    if not backward:
        return 4 * row + 2 * summary + 2 * stats
    return 8 * row + 4 * summary + 2 * stats


def core_cost(model, traffic, backward=True):
    """(flops, bytes) of the EVA core's local and remote parts for ONE
    minibatch, every layer: forward alone (a validation minibatch) or
    forward + backward (an optimizer step). What ``eva_attn_roofline``
    holds the kernels' time to."""
    seq, batch = traffic["seq_len"], traffic["minibatch"]
    layers = len(model["layers"])
    flops = layers * batch * core_flops(model, seq, backward)
    return flops, layers * core_bytes(model, batch * seq, backward)


def matmul_params(model):
    """Parameters a token meets in a matrix multiplication: each layer's
    projections and SwiGLU, once the heads."""
    return len(model["layers"]) * (attention_params(model)
                                   + ffn_params(model)) \
        + head_params(model)


def train_flops_by_part(model, seq):
    """{part: forward + backward FLOPs of one sequence}: the
    projections, the SwiGLU, the local triangles, the remote prefixes,
    the summaries and the heads."""
    layers = len(model["layers"])
    return {
        "projections": 6.0 * seq * layers * attention_params(model),
        "ffn": 6.0 * seq * layers * ffn_params(model),
        "local": layers * core_flops(model, seq, True, "local"),
        "remote": layers * core_flops(model, seq, True, "remote"),
        "summaries": layers * summary_flops(model, seq, True),
        "heads": 6.0 * seq * head_params(model)}


def train_flops_per_sample(model, traffic):
    """A sample is one sequence of ``traffic["seq_len"]`` bytes."""
    return sum(train_flops_by_part(model, traffic["seq_len"]).values())
