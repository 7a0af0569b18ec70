"""Operations and bytes of the decoder-only LM (``configs/lm110m.json``
and any configuration of the same block), computed from its shapes.

The arithmetic is the repo's ``bench.lm_train_flops_per_token``, copied
here so that a change to the program cannot move the yardstick: the
operations a perfect implementation NEEDS, causal attention counted over
the S(S+1)/2 visible pairs, the embedding gather not counted as a matrix
multiplication, recomputed operations (the flash backward's second
QK^T) not counted at all.
"""

BF16 = 2
F32 = 4


def matmul_params(model):
    """Parameters that sit in a matrix multiplication: per layer the
    fused qkv (d x 3d) and output (d x d) projections and the two FFN
    matrices (d x f, f x d); once the vocabulary head (d x V)."""
    d, f = model["dim"], model["ffn_hidden"]
    return model["layers"] * (4 * d * d + 2 * d * f) + d * model["vocab"]


def attention_flops_per_sequence(model, seq, passes=3):
    """Score and context matmuls of every layer for one sequence:
    2 FLOP x 2 matmuls x head_dim over the S(S+1)/2 causal pairs of
    each head = 2 S (S+1) dim per layer forward; the backward needs
    four such matmuls (dV, dP, dQ, dK), twice the forward.
    ``passes`` is 1 for the forward alone, 3 for forward + backward."""
    return passes * 2.0 * model["layers"] * seq * (seq + 1) * model["dim"]


def train_flops_per_token(model, seq):
    """Forward + backward FLOPs one trained token needs: 6 per matmul
    parameter (2 forward, 4 backward) plus its share of attention."""
    return 6.0 * matmul_params(model) \
        + attention_flops_per_sequence(model, seq) / seq


def train_flops_per_sample(model, traffic):
    """A sample is one sequence of ``traffic["seq_len"]`` tokens."""
    seq = traffic["seq_len"]
    return train_flops_per_token(model, seq) * seq


def attention_kernel_cost(model, traffic):
    """(flops, bytes) the attention kernels of ONE optimizer step need,
    forward and backward of all layers together, whole batch.

    Bytes are the least HBM traffic of a flash formulation in the
    compute type (bf16): the forward reads q, k, v and writes out; the
    backward reads q, k, v, out, dout and writes dq, dk, dv — twelve
    (B, S, dim) tensors per layer — plus the float32 row statistics
    (lse written once, read once)."""
    seq, batch = traffic["seq_len"], traffic["minibatch"]
    flops = batch * attention_flops_per_sequence(model, seq)
    tensor = batch * seq * model["dim"] * BF16
    rows = batch * model["heads"] * seq * F32
    return flops, model["layers"] * (12.0 * tensor + 2.0 * rows)
