"""Plain reference of the Ouro looped decoder (``configs/ouro_2_6b.json``).

Written from the equations as the configuration file states them, in
``jax.numpy`` and float32 under
``jax.default_matmul_precision("highest")`` — no kernels, no bf16, no
loop in the compiled sense (a Python ``for`` over the passes), nothing
imported from the program. It takes the program's weights and the
benchmark's own statement of the architecture (the configuration
file's ``model``). ``x`` is one sequence, (S, d);
``rms(x; g) = x * rsqrt(mean(x^2) + eps) * g``; no bias but the gate's.

    layer : a = x + rms(Attn(rms(x; g1)); g2)
            y = a + rms(SwiGLU(rms(a; g3)); g4)
    Attn  : q, k, v = split(n W_qkv) (H heads of dh); q, k <- rope
            (half-split rotation at theta, NO q/k norm);
            merge(softmax(q k^T / sqrt(dh) + causal) v) W_o
    SwiGLU: (silu(n W1) * (n W3)) W2
    loop  : h_0 = E[tokens];  h_t = rms(Stack(h_{t-1}); g_f),
            t = 1..T, Stack the SAME layers each pass
    exits : logits_t = h_t W_head; CE_t = -log softmax(logits_t)[next];
            lambda_t = sigmoid(h_t . w_gate + b_gate)
            p_t = lambda_t prod_{j<t} (1 - lambda_j)  (t < T),
            p_T = prod_{j<T} (1 - lambda_j)
    loss  : mean over tokens of [sum_t p_t CE_t - beta H(p)],
            H(p) = -sum_t p_t log p_t

Departures from the published description (each under the
configuration file's ``assumed``): the objective above with beta =
``exit_entropy_weight`` stands for the model's pre-training objective;
the gate reads the normed state; ``early_exit_threshold`` is unused (it
is an inference setting); momentum SGD, not the optimizer the model was
trained with.

Training steps are momentum SGD, ``v <- m v - lr g; w <- w + v``, with
``g`` from ``jax.grad`` of the loss above — ONE tree of layer weights,
used ``T`` times, so a weight's gradient is jax's own sum over its
uses — one sequence at a time and the gradients averaged. Parameters
and velocity live on the HOST as numpy arrays and the update is
numpy's: the program's weights and momentum are still on the chip when
the check runs.

Memory at S = 8192, T = 4: the loss (``sequence_loss``, one jitted
function) has every layer application and every pass's exit under
``jax.checkpoint``, the attention's query blocks and the exit's token
blocks too: neither the score blocks nor more than one token block's
logits (block x V float32) are alive at a time. Its whole ``jax.grad``
still wants 10.4 GB on the chip (XLA runs the checkpointed layers'
repeated forwards early and holds their residuals together), which
does not fit beside the program's state: ``sequence_gradients`` walks
the same chain rule one layer's ``jax.vjp`` at a time.
"""

import functools
import time

import jax
import jax.numpy as jnp
import numpy

#: Applied to both operands of every matrix product. The identity: the
#: reference is float32. A precision experiment (PERF.md section 6)
#: puts a rounding here and calls ``jax.clear_caches()``.
round_operand = None


def mm(a, b):
    if round_operand is not None:
        a, b = round_operand(a), round_operand(b)
    return a @ b


def ein(spec, a, b):
    if round_operand is not None:
        a, b = round_operand(a), round_operand(b)
    return jnp.einsum(spec, a, b)


def depth(model):
    layers = model["layers"]
    return layers if isinstance(layers, int) else len(layers)


def from_program(units, model):
    """``units``: [(kind, {name: array})] of the program's forward
    units in order, as ``export_params()`` gives them; -> the
    reference's parameter tree (numpy, on the host). The shapes are
    checked against the configuration file, so a program that quietly
    trained another width fails here and not in a tolerance."""
    d, v, f = model["dim"], model["vocab"], model["ffn_hidden"]
    wide = model["heads"] * model["head_dim"]
    shapes = {
        "embedding": {"weights": (v, d)},
        "gqa_attention": {"weights": (d, 3 * wide),
                          "weights_out": (wide, d), "norm": (d,),
                          "norm_out": (d,)},
        "swiglu_ffn": {"weights": (d, 2 * f), "weights2": (f, d),
                       "norm": (d,), "norm_out": (d,)},
        "rms_norm": {"weights": (d,)},
        "exit_gate": {"weights": (d,), "gate_bias": (1,)},
        "token_dense": {"weights": (d, v)},
    }
    want = ["embedding"] + ["gqa_attention", "swiglu_ffn"] * depth(model) \
        + ["rms_norm", "exit_gate", "token_dense"]
    kinds = [k for k, _ in units]
    if kinds != want:
        raise ValueError("program's layers %r are not the "
                         "configuration's %r" % (kinds, want))
    arrays = []
    for kind, params in units:
        got = {k: tuple(a.shape) for k, a in params.items()}
        if got != shapes[kind]:
            raise ValueError("%s unit has %r, the configuration says %r"
                             % (kind, got, shapes[kind]))
        arrays.append({k: numpy.asarray(a, numpy.float32)
                       for k, a in params.items()})
    body = arrays[1:-3]
    return {"embedding": arrays[0]["weights"],
            "layers": [{"attn": body[2 * i], "ffn": body[2 * i + 1]}
                       for i in range(len(body) // 2)],
            "out_norm": arrays[-3]["weights"],
            "gate": arrays[-2],
            "head": arrays[-1]["weights"]}


# -- the layers ------------------------------------------------------------


def rms(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * gain


def rope_tables(seq, dh, theta):
    """(cos, sin), each (seq, dh) float32, made on the host in float64
    (the two halves of a head turn by the same angles)."""
    inv = theta ** (-numpy.arange(0, dh, 2, dtype=numpy.float64) / dh)
    angle = numpy.arange(seq, dtype=numpy.float64)[:, None] * inv[None]
    angle = numpy.concatenate([angle, angle], axis=-1)
    return (numpy.cos(angle).astype(numpy.float32),
            numpy.sin(angle).astype(numpy.float32))


def rotate_half(t):
    a, b = jnp.split(t, 2, axis=-1)
    return jnp.concatenate([-b, a], axis=-1)


def attention(x, p, model, tables, q_block):
    """Attn on normalised input (S, d); the queries are taken
    ``q_block`` at a time so that the score matrix is (H, q_block, S)."""
    s = x.shape[0]
    h, dh = model["heads"], model["head_dim"]
    cos, sin = tables
    w_q, w_k, w_v = jnp.split(p["weights"], 3, axis=1)

    def heads(t):
        return t.reshape(s, h, dh).transpose(1, 0, 2)

    q, k, v = heads(mm(x, w_q)), heads(mm(x, w_k)), heads(mm(x, w_v))
    q = q * cos + rotate_half(q) * sin
    k = k * cos + rotate_half(k) * sin
    cols = jnp.arange(s)

    @jax.checkpoint
    def block(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, q_block, axis=1)
        scores = ein("hqd,hkd->hqk", qb, k) / numpy.sqrt(dh)
        rows = start + jnp.arange(q_block)
        hidden = cols[None, None, :] > rows[None, :, None]
        probs = jax.nn.softmax(jnp.where(hidden, -jnp.inf, scores), -1)
        return ein("hqk,hkd->hqd", probs, v)

    out = jax.lax.map(block, jnp.arange(0, s, q_block))   # (n, h, qb, dh)
    merged = out.transpose(0, 2, 1, 3).reshape(s, h * dh)
    return mm(merged, p["weights_out"])


def swiglu(x, p):
    h1, h3 = jnp.split(mm(x, p["weights"]), 2, axis=-1)
    return mm(jax.nn.silu(h1) * h3, p["weights2"])


def layer(x, p, model, tables, q_block):
    eps = model["norm_eps"]
    attn, ffn = p["attn"], p["ffn"]
    a = x + rms(attention(rms(x, attn["norm"], eps), attn, model, tables,
                          q_block), attn["norm_out"], eps)
    return a + rms(swiglu(rms(a, ffn["norm"], eps), ffn),
                   ffn["norm_out"], eps)


def states(tree, tokens, tables, model, q_block):
    """[h_1 .. h_T], each (S, d): the state after every pass."""
    block = jax.checkpoint(functools.partial(
        layer, model=model, tables=tables, q_block=q_block))
    h = tree["embedding"][tokens]
    out = []
    for _ in range(model["ut_steps"]):
        for p in tree["layers"]:
            h = block(h, p)
        h = rms(h, tree["out_norm"], model["norm_eps"])
        out.append(h)
    return out


def exit_terms(tree, h, labels, token_block):
    """(CE, gate) of one pass's state, each (S,): the cross entropy of
    every token at this exit, ``token_block`` tokens' logits at a time,
    and the gate's logit."""
    @jax.checkpoint
    def block(args):
        rows, want = args
        logp = jax.nn.log_softmax(mm(rows, tree["head"]), axis=-1)
        return -jnp.take_along_axis(logp, want[:, None], axis=-1)[:, 0]

    n = h.shape[0] // token_block
    ce = jax.lax.map(block, (h.reshape(n, token_block, -1),
                             labels.reshape(n, token_block)))
    gate = h @ tree["gate"]["weights"] + tree["gate"]["gate_bias"]
    return ce.reshape(-1), gate


def exit_mass(gates):
    """p (T, S) from the gates' logits (T, S)."""
    lam = jax.nn.sigmoid(gates)
    stay = jnp.cumprod(1.0 - lam, axis=0)
    before = jnp.concatenate([jnp.ones_like(stay[:1]), stay[:-1]])
    return jnp.concatenate([lam[:-1] * before[:-1], before[-1:]])


def expected_loss(ce, gates, beta):
    """sum over tokens of [sum_t p_t CE_t - beta H(p)] from the exits'
    cross entropies and gate logits, each (T, S)."""
    p = exit_mass(gates)
    entropy = -(p * jnp.log(jnp.maximum(p, 1e-30))).sum(0)
    return ((p * ce).sum(0) - beta * entropy).sum()


def sequence_exits(tree, tokens, labels, tables, model, q_block,
                   token_block):
    """(CE, gate logits), each (T, S)."""
    exit_block = jax.checkpoint(functools.partial(
        exit_terms, token_block=token_block))
    terms = [exit_block(tree, h, labels)
             for h in states(tree, tokens, tables, model, q_block)]
    return (jnp.stack([t[0] for t in terms]),
            jnp.stack([t[1] for t in terms]))


def sequence_terms(tree, tokens, labels, tables, model, q_block,
                   token_block):
    """(CE, p), each (T, S)."""
    ce, gates = sequence_exits(tree, tokens, labels, tables, model,
                               q_block, token_block)
    return ce, exit_mass(gates)


def sequence_loss(tree, tokens, labels, tables, model, q_block,
                  token_block):
    """The loss above, summed over one sequence's tokens."""
    ce, gates = sequence_exits(tree, tokens, labels, tables, model,
                               q_block, token_block)
    return expected_loss(ce, gates, model["exit_entropy_weight"])


# -- loss and training -----------------------------------------------------


def _model(key):
    return {k: (list(v) if isinstance(v, tuple) else v) for k, v in key}


def _key(model):
    """The model's shape as a hashable static argument."""
    def frozen(v):
        return tuple(v) if isinstance(v, list) else v
    return tuple(sorted(
        (k, frozen(v)) for k, v in model.items()
        if isinstance(v, (int, float, str, list))))


def _blocks(seq):
    return {"q_block": min(seq, 512), "token_block": min(seq, 1024)}


@functools.partial(jax.jit, static_argnames=("model_key",))
def _sequence_loss(tree, tokens, labels, tables, model_key):
    with jax.default_matmul_precision("highest"):
        return sequence_loss(tree, tokens, labels, tables,
                             _model(model_key),
                             **_blocks(tokens.shape[0]))


@functools.lru_cache(maxsize=None)
def _stages(model_key, seq, exit_fn, total_fn, rounding):
    """The pieces :func:`sequence_gradients` walks, each jitted once:
    a layer and its pullback, the final norm and its pullback, an exit
    and its pullback, the loss over the exits with its gradient.
    ``rounding`` is ``round_operand`` at the time: a key, since the
    pieces read it when they are traced."""
    model = _model(model_key)
    blocks = _blocks(seq)
    tables = rope_tables(seq, model["head_dim"], model["rope_theta"])

    def precise(fn):
        def run(*args):
            with jax.default_matmul_precision("highest"):
                return fn(*args)
        return jax.jit(run)

    def one_layer(p, x):
        return layer(x, p, model, tables, blocks["q_block"])

    def out_norm(g, x):
        return rms(x, g, model["norm_eps"])

    def one_exit(shared, h, labels):
        return exit_fn(shared, h, labels, blocks["token_block"])

    def total(ce, gates):
        return total_fn(ce, gates, model["exit_entropy_weight"])

    def pullback(fn):
        """(cotangent, parameters, input, *rest) -> the cotangents of
        the parameters and the input."""
        def back(cotangent, p, x, *rest):
            return jax.vjp(lambda p, x: fn(p, x, *rest), p, x)[1](
                cotangent)
        return precise(back)

    return {"layer": precise(one_layer), "layer_back": pullback(one_layer),
            "norm": precise(out_norm), "norm_back": pullback(out_norm),
            "exit": precise(one_exit), "exit_back": pullback(one_exit),
            "total": precise(jax.value_and_grad(total, argnums=(0, 1)))}


def sequence_gradients(tree, tokens, labels, model, exit_fn=exit_terms,
                       total_fn=expected_loss, counts=lambda t: True):
    """(value, gradient tree on the host) of :func:`sequence_loss` for
    one sequence — ``jax.grad``'s result (a test holds it to that),
    with the chain rule over the passes and layers walked by hand, one
    jitted pullback of ONE layer (or exit) at a time: at the timed
    sizes the whole function's ``jax.grad`` wants 10.4 GB beside the
    tree, its gradient and the program's own state, and a layer's wants
    2. The layer inputs and the gradient sums wait on the host.
    ``tree`` is on the device.

    ``exit_fn`` / ``total_fn`` stand for :func:`exit_terms` /
    :func:`expected_loss`, and ``counts(t)`` says whether pass ``t``'s
    use of the shared parameters adds to their gradient (the cotangent
    of the state flows on regardless): the seams where
    ``benchmark/tests/chip_grads_ouro.py`` plants its faults."""
    stage = _stages(_key(model), len(tokens), exit_fn, total_fn,
                    round_operand)
    shared = {k: tree[k] for k in ("head", "gate")}
    passes = range(1, model["ut_steps"] + 1)
    h = tree["embedding"][tokens]
    inputs, normed, terms = [], [], []
    for _ in passes:
        for p in tree["layers"]:
            inputs.append(numpy.asarray(h))
            h = stage["layer"](p, h)
        inputs.append(numpy.asarray(h))
        h = stage["norm"](tree["out_norm"], h)
        normed.append(numpy.asarray(h))
        terms.append(stage["exit"](shared, h, labels))
    ce = jnp.stack([t[0] for t in terms])
    gates = jnp.stack([t[1] for t in terms])
    value, (dce, dgates) = stage["total"](ce, gates)

    grads = jax.tree_util.tree_map(
        lambda a: numpy.zeros(a.shape, numpy.float32), tree)

    def add(into, g, t):
        if counts(t):
            jax.tree_util.tree_map(
                lambda a, b: numpy.add(a, numpy.asarray(b), out=a),
                into, g)

    dh = jnp.zeros_like(h)
    for t in reversed(passes):
        dshared, dstate = stage["exit_back"](
            (dce[t - 1], dgates[t - 1]), shared, normed.pop(), labels)
        for key in shared:
            add(grads[key], dshared[key], t)
        dnorm, dh = stage["norm_back"](dh + dstate, tree["out_norm"],
                                       inputs.pop())
        add(grads["out_norm"], dnorm, t)
        for index in reversed(range(len(tree["layers"]))):
            dp, dh = stage["layer_back"](dh, tree["layers"][index],
                                         inputs.pop())
            add(grads["layers"][index], dp, t)
    numpy.add.at(grads["embedding"], numpy.asarray(tokens),
                 numpy.asarray(dh))
    return float(value), grads


def _batch(batch, model):
    tokens, labels = (numpy.asarray(a, numpy.int32) for a in batch)
    tables = rope_tables(tokens.shape[1], model["head_dim"],
                         model["rope_theta"])
    return tokens, labels, tables


def loss(tree, batch, model):
    """Mean loss of ``batch`` = (tokens, labels), (B, S) integer
    arrays."""
    tokens, labels, tables = _batch(batch, model)
    on_device = jax.device_put(tree)
    total = sum(float(_sequence_loss(on_device, t, l, tables,
                                     _key(model)))
                for t, l in zip(tokens, labels))
    return total / tokens.size


def gradients(tree, batch, model):
    """(mean loss, its gradient tree as numpy arrays on the host)."""
    tokens, labels, _ = _batch(batch, model)
    on_device = jax.block_until_ready(jax.device_put(tree))
    total, grads = 0.0, None
    for t, l in zip(tokens, labels):
        value, g = sequence_gradients(on_device, t, l, model)
        total += value
        if grads is None:
            grads = g
        else:
            jax.tree_util.tree_map(
                lambda a, b: numpy.add(a, b, out=a), grads, g)
    scale = numpy.float32(1.0 / tokens.size)
    jax.tree_util.tree_map(lambda a: numpy.multiply(a, scale, out=a),
                           grads)
    return total / tokens.size, grads


def train(tree, batches, model, lr, moment):
    """Momentum SGD over ``batches`` in order; -> (tree after the last
    step, [loss of each batch before its step])."""
    tree = jax.tree_util.tree_map(
        lambda a: numpy.array(a, numpy.float32), tree)
    velocity = jax.tree_util.tree_map(numpy.zeros_like, tree)
    lr, moment = numpy.float32(lr), numpy.float32(moment)
    losses = []
    start = time.perf_counter()
    for batch in batches:
        value, grads = gradients(tree, batch, model)
        losses.append(value)

        def update(w, v, g):
            v *= moment
            g *= -lr
            v += g                              # v <- m v - lr g
            w += v
        jax.tree_util.tree_map(update, tree, velocity, grads)
    print("reference train: %d steps, %.1f s" % (
        len(losses), time.perf_counter() - start), flush=True)
    return tree, losses
