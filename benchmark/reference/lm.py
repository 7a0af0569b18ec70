"""Plain reference of the decoder-only LM (``configs/lm110m.json``).

Written from the layer equations of Vaswani et al. 2017 as the
configuration file states them, in ``jax.numpy`` and float32 under
``jax.default_matmul_precision("highest")`` — no kernels, no cache, no
bf16, nothing imported from the program. It takes the program's weights
(so that both sides compute the same function) and the benchmark's own
statement of the architecture (the configuration file's ``model``).

    h0      = E[tokens] + PE                      (sinusoidal PE, added)
    a       = h + (softmax(mask(Q K^T / sqrt(dh))) V) Wo + bo,
              [Q K V] = h Wqkv + bqkv             (12 heads of 64)
    h'      = LayerNorm(a)                        (post-LN)
    f       = h' + relu(h' W1 + b1) W2 + b2
    h''     = LayerNorm(f)
    logits  = h_L Wv + bv
    loss    = mean over tokens of -log softmax(logits)[next token]

Training steps are momentum SGD, ``v <- m v - lr g; w <- w + v``, on
every parameter, with ``g`` from ``jax.grad`` of the loss above.

Departures from the paper, as the program makes them: no sqrt(dim)
scaling of the embedding, biases on all four attention projections,
an untied vocabulary head, no dropout.

The layers are walked with ``lax.scan`` over their stacked parameters:
the loop is the same, but the compiler sees one layer, so the program
compiles in seconds and is a few megabytes in the compile cache — beside
a 100 MB step program under a capped cache that is what keeps both.
"""

import functools

import jax
import jax.numpy as jnp
import numpy

#: the order of unit kinds in one block, as the program's layer list
#: names them (``MAPPING`` of each forward unit)
BLOCK = ("attention", "layernorm", "transformer_ffn", "layernorm")


def from_program(units, model):
    """``units``: [(kind, {name: array})] of the program's forward
    units in order, as ``export_params()`` gives them; -> the
    reference's parameter tree. The shapes are checked against the
    configuration file, so a program that quietly trained another
    width fails here and not in a tolerance."""
    d, f, v = model["dim"], model["ffn_hidden"], model["vocab"]
    kinds = [k for k, _ in units]
    want = ["embedding"] + list(BLOCK) * model["layers"] + ["token_dense"]
    if kinds != want:
        raise ValueError("program's layers %r are not the "
                         "configuration's %r" % (kinds, want))
    shapes = {
        "embedding": {"weights": (v, d)},
        "attention": {"weights": (d, 3 * d), "bias": (3 * d,),
                      "weights_out": (d, d), "bias_out": (d,)},
        "layernorm": {"weights": (d,), "bias": (d,)},
        "transformer_ffn": {"weights": (d, f), "bias": (f,),
                            "weights2": (f, d), "bias2": (d,)},
        "token_dense": {"weights": (d, v), "bias": (v,)},
    }
    layers = []
    for kind, params in units:
        got = {k: tuple(a.shape) for k, a in params.items()}
        if got != shapes[kind]:
            raise ValueError("%s unit has %r, the configuration says %r"
                             % (kind, got, shapes[kind]))
        layers.append({k: jnp.asarray(a, jnp.float32)
                       for k, a in params.items()})
    blocks = [layers[1 + 4 * i:5 + 4 * i] for i in range(model["layers"])]
    # [attention, layernorm, ffn, layernorm], each stacked over layers
    stacked = [jax.tree_util.tree_map(lambda *leaves: jnp.stack(leaves),
                                      *(block[j] for block in blocks))
               for j in range(len(BLOCK))]
    return {"embedding": layers[0], "blocks": stacked, "head": layers[-1]}


def positions(seq, dim):
    """PE[pos, 2i] = sin(pos / 10000^(2i/dim)), PE[pos, 2i+1] = cos.
    Made on the host in float64 and handed to the jitted functions as
    an argument: as a constant, the (8192, 768) table alone made the
    compiled reference 70 MB in the compile cache."""
    pos = numpy.arange(seq, dtype=numpy.float64)[:, None]
    i = numpy.arange(dim)[None, :]
    angle = pos / numpy.power(10000.0, (2 * (i // 2)) / dim)
    return numpy.where(i % 2 == 0, numpy.sin(angle),
                       numpy.cos(angle)).astype(numpy.float32)


def layer_norm(x, gain, bias, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * gain + bias


def attention(x, p, heads, q_block):
    """Causal self-attention of ONE sequence ``x`` (S, D), the queries
    taken ``q_block`` at a time so that the score matrix is
    (heads, q_block, S) and an S of 8192 fits."""
    s, d = x.shape
    dh = d // heads
    qkv = x @ p["weights"] + p["bias"]
    q, k, v = (t.reshape(s, heads, dh).transpose(1, 0, 2)
               for t in jnp.split(qkv, 3, axis=-1))
    cols = jnp.arange(s)

    def block(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, q_block, axis=1)
        scores = jnp.einsum("hqd,hkd->hqk", qb, k) / numpy.sqrt(dh)
        rows = start + jnp.arange(q_block)
        hidden = cols[None, None, :] > rows[None, :, None]
        probs = jax.nn.softmax(jnp.where(hidden, -jnp.inf, scores), axis=-1)
        return jnp.einsum("hqk,hkd->hqd", probs, v)

    out = jax.lax.map(block, jnp.arange(0, s, q_block))  # (S/qb, H, qb, dh)
    merged = out.transpose(0, 2, 1, 3).reshape(s, d)
    return x + merged @ p["weights_out"] + p["bias_out"]


def ffn(x, p):
    hidden = jnp.maximum(x @ p["weights"] + p["bias"], 0.0)
    return x + hidden @ p["weights2"] + p["bias2"]


def sequence_loss(tree, tokens, labels, pe, model, q_block):
    """Summed next-token cross-entropy of one sequence; ``pe`` is the
    (S, dim) table of :func:`positions`."""
    eps = model["layernorm_eps"]

    def block(h, params):
        attn, ln1, ff, ln2 = params
        h = layer_norm(attention(h, attn, model["heads"], q_block),
                       ln1["weights"], ln1["bias"], eps)
        return layer_norm(ffn(h, ff), ln2["weights"], ln2["bias"], eps), None

    h = tree["embedding"]["weights"][tokens] + pe
    h, _ = jax.lax.scan(block, h, tree["blocks"])
    logits = h @ tree["head"]["weights"] + tree["head"]["bias"]
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, labels[:, None], axis=-1).sum()


def micro_batches(tokens, labels, micro):
    """(B, S) -> (B / micro, micro, S): float32 activations of a
    32,768-token batch do not fit beside the program's own state, so
    the batch is taken ``micro`` sequences at a time."""
    b, s = tokens.shape
    micro = min(micro, b)
    if b % micro:
        raise ValueError("batch %d is not a multiple of %d" % (b, micro))
    return (tokens.reshape(b // micro, micro, s),
            labels.reshape(b // micro, micro, s))


def summed_loss(tree, tokens, labels, pe, model):
    """Summed loss of a few sequences at once."""
    one = functools.partial(sequence_loss, pe=pe, model=model,
                            q_block=min(tokens.shape[1], 512))
    return jax.vmap(one, in_axes=(None, 0, 0))(tree, tokens, labels).sum()


def batch_loss(tree, tokens, labels, pe, model, micro=4):
    """Mean loss per token of a (B, S) batch."""
    sums = jax.lax.map(lambda tl: summed_loss(tree, *tl, pe, model),
                       micro_batches(tokens, labels, micro))
    return sums.sum() / tokens.size


def batch_loss_and_grads(tree, tokens, labels, pe, model, micro=4):
    """Mean loss per token and its gradient, accumulated over the
    micro-batches (the gradient of each is taken inside the loop, so
    only one micro-batch's activations are alive at a time)."""
    def body(carry, tl):
        loss, grads = jax.value_and_grad(summed_loss)(tree, *tl, pe, model)
        return (carry[0] + loss,
                jax.tree_util.tree_map(jnp.add, carry[1], grads)), None

    zero = (jnp.float32(0.0), jax.tree_util.tree_map(jnp.zeros_like, tree))
    (loss, grads), _ = jax.lax.scan(
        body, zero, micro_batches(tokens, labels, micro))
    return loss / tokens.size, jax.tree_util.tree_map(
        lambda g: g / tokens.size, grads)


@functools.partial(jax.jit, static_argnames=("model_key",))
def _loss(tree, tokens, labels, pe, model_key):
    with jax.default_matmul_precision("highest"):
        return batch_loss(tree, tokens, labels, pe, dict(model_key))


@functools.partial(jax.jit, static_argnames=("model_key",),
                   donate_argnums=(0, 1))
def _train_step(tree, velocity, tokens, labels, pe, lr, moment, model_key):
    with jax.default_matmul_precision("highest"):
        loss, grads = batch_loss_and_grads(tree, tokens, labels, pe,
                                           dict(model_key))
    velocity = jax.tree_util.tree_map(
        lambda v, g: moment * v - lr * g, velocity, grads)
    tree = jax.tree_util.tree_map(lambda w, v: w + v, tree, velocity)
    return tree, velocity, loss


def _key(model):
    """The model's numbers as a hashable static argument."""
    return tuple(sorted((k, v) for k, v in model.items()
                        if isinstance(v, (int, float))))


def loss(tree, batch, model):
    """Mean next-token loss of ``batch`` = (tokens, labels), (B, S)
    integer arrays."""
    tokens, labels = (jnp.asarray(a, jnp.int32) for a in batch)
    pe = positions(tokens.shape[1], model["dim"])
    return float(_loss(tree, tokens, labels, pe, _key(model)))


def train(tree, batches, model, lr, moment):
    """Momentum SGD over ``batches`` in order; -> (tree after the last
    step, [loss of each batch before its step])."""
    velocity = jax.tree_util.tree_map(jnp.zeros_like, tree)
    tree = jax.tree_util.tree_map(jnp.copy, tree)
    losses = []
    pe = jnp.asarray(positions(batches[0][0].shape[1], model["dim"]))
    for tokens, labels in batches:
        tree, velocity, step_loss = _train_step(
            tree, velocity, jnp.asarray(tokens, jnp.int32),
            jnp.asarray(labels, jnp.int32), pe, numpy.float32(lr),
            numpy.float32(moment), _key(model))
        losses.append(step_loss)
    return tree, [float(v) for v in losses]
