"""Operations and bytes of the Ouro looped decoder
(``configs/ouro_2_6b.json``), computed from its shapes: what a perfect
implementation NEEDS. The ``layers`` layers run ``ut_steps`` times a
token, and every pass exits through the vocabulary head, so a token
meets ``ut_steps x layers`` layer applications and ``ut_steps`` heads.
Causal attention is counted over the S(S+1)/2 visible pairs, the
embedding gather is no matrix multiplication, and RECOMPUTED operations
are not counted: the program runs every layer's forward a second time
in its backward, which is time and not work.
"""

BF16 = 2
F32 = 4


def depth(model):
    layers = model["layers"]
    return layers if isinstance(layers, int) else len(layers)


def applications(model):
    """Layer applications a token passes through."""
    return model["ut_steps"] * depth(model)


def layer_params(model):
    """Matrix parameters of one layer: W_qkv (d x 3 H dh), W_o
    (H dh x d), and the SwiGLU's W1, W3 (d x f) and W2 (f x d)."""
    d, wide = model["dim"], model["heads"] * model["head_dim"]
    return 4 * d * wide + 3 * d * model["ffn_hidden"]


def exit_params(model):
    """Of one exit: the head (d x V) and the gate (d)."""
    return model["dim"] * (model["vocab"] + 1)


def matmul_params_met(model):
    """Matrix parameters a token meets, every use counted."""
    return applications(model) * layer_params(model) \
        + model["ut_steps"] * exit_params(model)


def attention_flops_per_sequence(model, seq, passes=3):
    """Score and context matmuls of every layer application for one
    sequence: 2 FLOP x 2 matmuls x head_dim over the S(S+1)/2 causal
    pairs of each head; the backward needs four such matmuls.
    ``passes``: 1 forward alone, 3 forward + backward."""
    wide = model["heads"] * model["head_dim"]
    return passes * 2.0 * applications(model) * seq * (seq + 1) * wide


def train_flops_per_token(model, seq):
    """Forward + backward FLOPs one trained token needs: 6 per matmul
    parameter it meets (2 forward, 4 backward) plus its share of
    attention — three forwards' worth."""
    return 6.0 * matmul_params_met(model) \
        + attention_flops_per_sequence(model, seq) / seq


def train_flops_per_sample(model, traffic):
    """A sample is one sequence of ``traffic["seq_len"]`` tokens."""
    seq = traffic["seq_len"]
    return train_flops_per_token(model, seq) * seq


def attention_kernel_cost(model, traffic, backward=True):
    """(flops, bytes) the attention kernels need for ONE minibatch,
    every layer application: forward alone (a validation minibatch) or
    forward + backward (an optimizer step; the backward's repeated
    forward is not counted). Bytes as for ``costs/lm.py``, the least of
    a flash formulation in bf16: four (B, S, H dh) tensors an
    application forward (q, k, v read, out written) and eight more
    backward, plus the float32 row statistics."""
    seq, batch = traffic["seq_len"], traffic["minibatch"]
    passes, tensors, stats = (3, 12.0, 2.0) if backward else (1, 4.0, 1.0)
    flops = batch * attention_flops_per_sequence(model, seq, passes)
    tensor = batch * seq * model["heads"] * model["head_dim"] * BF16
    rows = batch * model["heads"] * seq * F32
    return flops, applications(model) * (tensors * tensor + stats * rows)
