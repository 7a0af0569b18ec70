"""Plain reference of the LFM2-MoE decoder (``configs/lfm2_24b_a2b.json``).

Written from the layer equations as the configuration file states them,
in ``jax.numpy`` and float32 under
``jax.default_matmul_precision("highest")`` — no kernels, no sort, no
grouped product, no bf16, nothing imported from the program. It takes
the program's weights (so that both sides compute the same function)
and the benchmark's own statement of the architecture (the
configuration file's ``model``). ``x`` is one sequence, (S, d);
``rms(x; g) = x * rsqrt(mean(x^2) + eps) * g``; no bias anywhere.

    h0     = E[tokens]                           (no absolute positions)
    block  : a = h + Op(rms(h; g_op));  h' = a + FFN(rms(a; g_ffn))
    conv   : (Bg, Cg, u) = split3(n W_in); v = Bg * u;
             c_t = sum_j w[:, j] * v_{t-(L-1)+j}  (zeros before t = 0);
             Op = (Cg * c) W_out
    attn   : q = n W_q (H heads), k = n W_k, v = n W_v (KV heads);
             q, k <- rope(rms(q; g_q)), rope(rms(k; g_k)) over the
             head's width, half-split rotation at theta; query head i
             reads K/V head i // (H / KV);
             Op = merge(softmax(q k^T / sqrt(dh) + causal) v) W_o
    dense  : FFN = (silu(n W1) * (n W3)) W2
    expert : s = sigmoid(n W_r); selected = top-k of s + b;
             p = s[selected] / (sum p + 1e-6) * scaling;
             FFN = sum over selected AND held e of
                   p_e * (silu(n W1_e) * (n W3_e)) W2_e
    logits = rms(h_L; g_out) W_head
    loss   = mean over tokens of -log softmax(logits)[next token]

The expert layer is a plain loop over the experts ``experts_held`` says
this chip holds, each applied to every token under a mask; what the
other experts would add is left out, as in the program (the chip's
share of an expert-parallel deployment). The bias ``b`` only selects,
and no gradient reaches it.

Training steps are momentum SGD, ``v <- m v - lr g; w <- w + v``, on
every parameter but ``b``, with ``g`` from ``jax.grad`` of the loss
above, one sequence at a time and the gradients averaged (exact:
nothing in the loss couples sequences). The parameters and the
velocity live on the HOST as numpy arrays and the update is numpy's:
the program's own weights and momentum (8 bytes a parameter) are still
on the chip when the check runs, and a second float32 model with its
velocity and gradients beside them does not fit 16 GB.

Memory at S = 8192: every block, the attention's query blocks and the
expert loop's iterations are under ``jax.checkpoint``, so the backward
keeps one block's activations at a time and neither the score blocks
nor every expert's hidden activations.
"""

import functools
import time

import jax
import jax.numpy as jnp
import numpy

KINDS = {"conv": "short_conv", "full_attention": "gqa_attention"}

#: Applied to both operands of every matrix product. The identity: the
#: reference is float32. A precision experiment (PERF.md section 6: what
#: would the loss read with fp8 operands?) puts a rounding here and
#: calls ``jax.clear_caches()``.
round_operand = None


def mm(a, b):
    if round_operand is not None:
        a, b = round_operand(a), round_operand(b)
    return a @ b


def ein(spec, a, b):
    if round_operand is not None:
        a, b = round_operand(a), round_operand(b)
    return jnp.einsum(spec, a, b)


def layer_kinds(model):
    """[(operator kind, FFN kind)] of the layers, as the program names
    its units."""
    return [(KINDS[kind], "swiglu_ffn" if i < model["dense_layers"]
             else "expert_ffn")
            for i, kind in enumerate(model["layers"])]


def held(model):
    lo, hi = model.get("experts_held") or (0, model["moe_experts"])
    return int(lo), int(hi)


def from_program(units, model):
    """``units``: [(kind, {name: array})] of the program's forward
    units in order, as ``export_params()`` gives them; -> the
    reference's parameter tree (numpy, on the host). The shapes are
    checked against the configuration file, so a program that quietly
    trained another width fails here and not in a tolerance."""
    d, v = model["dim"], model["vocab"]
    h, kv, dh = model["heads"], model["kv_heads"], model["head_dim"]
    f, fe, e = model["ffn_hidden"], model["moe_hidden"], \
        model["moe_experts"]
    lo, hi = held(model)
    shapes = {
        "embedding": {"weights": (v, d)},
        "short_conv": {"weights": (d, 3 * d),
                       "conv": (d, model["conv_kernel"]),
                       "weights_out": (d, d), "norm": (d,)},
        "gqa_attention": {"weights": (d, (h + 2 * kv) * dh),
                          "weights_out": (h * dh, d), "norm": (d,),
                          "q_norm": (dh,), "k_norm": (dh,)},
        "swiglu_ffn": {"weights": (d, 2 * f), "weights2": (f, d),
                       "norm": (d,)},
        "expert_ffn": {"weights": (d, e),
                       "weights13": (hi - lo, d, 2 * fe),
                       "weights2": (hi - lo, fe, d), "norm": (d,),
                       "expert_bias": (e,)},
        "rms_norm": {"weights": (d,)},
        "token_dense": {"weights": (d, v)},
    }
    kinds = [k for k, _ in units]
    want = ["embedding"] + [k for pair in layer_kinds(model)
                            for k in pair] + ["rms_norm", "token_dense"]
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
    body = arrays[1:-2]
    return {"embedding": arrays[0]["weights"],
            "layers": [{"op": body[2 * i], "ffn": body[2 * i + 1]}
                       for i in range(len(body) // 2)],
            "out_norm": arrays[-2]["weights"],
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


def short_conv(x, p, model):
    """The operator of a ``conv`` layer on normalised input (S, d)."""
    gate_in, gate_out, u = jnp.split(mm(x, p["weights"]), 3, axis=-1)
    v = gate_in * u
    taps = model["conv_kernel"]
    c = jnp.zeros_like(v)
    for j in range(taps):
        shift = taps - 1 - j            # tap j reads v_{t - shift}
        moved = v if shift == 0 else jnp.concatenate(
            [jnp.zeros_like(v[:shift]), v[:-shift]], axis=0)
        c = c + p["conv"][:, j] * moved
    return mm(gate_out * c, p["weights_out"])


def gqa_attention(x, p, model, tables, q_block):
    """The operator of a ``full_attention`` layer on normalised input
    (S, d); the queries are taken ``q_block`` at a time so that the
    score matrix is (H, q_block, S)."""
    s = x.shape[0]
    h, kv, dh = model["heads"], model["kv_heads"], model["head_dim"]
    eps = model["norm_eps"]
    cos, sin = tables
    w_q, w_k, w_v = jnp.split(p["weights"], [h * dh, (h + kv) * dh],
                              axis=1)

    def heads(t, n):
        return t.reshape(s, n, dh).transpose(1, 0, 2)

    q = rms(heads(mm(x, w_q), h), p["q_norm"], eps)
    k = rms(heads(mm(x, w_k), kv), p["k_norm"], eps)
    q = q * cos + rotate_half(q) * sin
    k = k * cos + rotate_half(k) * sin
    v = heads(mm(x, w_v), kv)
    q = q.reshape(kv, h // kv, s, dh)   # query head i: K/V head i // g
    cols = jnp.arange(s)

    @jax.checkpoint
    def block(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, q_block, axis=2)
        scores = ein("ngqd,nkd->ngqk", qb, k) / numpy.sqrt(dh)
        rows = start + jnp.arange(q_block)
        hidden = cols[None, None, None, :] > rows[None, None, :, None]
        probs = jax.nn.softmax(jnp.where(hidden, -jnp.inf, scores), -1)
        return ein("ngqk,nkd->ngqd", probs, v)

    out = jax.lax.map(block, jnp.arange(0, s, q_block))
    merged = out.transpose(0, 3, 1, 2, 4).reshape(s, h * dh)
    return mm(merged, p["weights_out"])


def swiglu_ffn(x, p):
    h1, h3 = jnp.split(mm(x, p["weights"]), 2, axis=-1)
    return mm(jax.nn.silu(h1) * h3, p["weights2"])


def route(x, p, model):
    """-> (selected (S, k) expert ids, their weights (S, k))."""
    scores = jax.nn.sigmoid(mm(x, p["weights"]))
    _, selected = jax.lax.top_k(
        scores + jax.lax.stop_gradient(p["expert_bias"]),
        model["moe_top_k"])
    weight = jnp.take_along_axis(scores, selected, axis=-1)
    weight = weight / (weight.sum(-1, keepdims=True) + 1e-6)
    return selected, weight * model.get("routed_scaling", 1.0)


def expert_ffn(x, p, model):
    """The expert layer on normalised input (S, d): the part of the sum
    that the experts ``experts_held`` names give."""
    lo, _ = held(model)
    selected, weight = route(x, p, model)

    @jax.checkpoint
    def one(y, expert):
        w13, w2, index = expert
        mine = jnp.where(selected == index, weight, 0.0).sum(-1)
        h1, h3 = jnp.split(mm(x, w13), 2, axis=-1)
        return y + mine[:, None] * mm(jax.nn.silu(h1) * h3, w2), None

    ids = lo + jnp.arange(p["weights13"].shape[0])
    y, _ = jax.lax.scan(one, jnp.zeros_like(x),
                        (p["weights13"], p["weights2"], ids))
    return y


def sequence_logits(tree, tokens, tables, model, q_block):
    """(S, V) logits of one sequence."""
    eps = model["norm_eps"]

    @functools.partial(jax.checkpoint, static_argnums=(2,))
    def block(h, layer, kinds):
        op, ffn = layer["op"], layer["ffn"]
        n = rms(h, op["norm"], eps)
        if kinds[0] == "short_conv":
            h = h + short_conv(n, op, model)
        else:
            h = h + gqa_attention(n, op, model, tables, q_block)
        n = rms(h, ffn["norm"], eps)
        if kinds[1] == "swiglu_ffn":
            return h + swiglu_ffn(n, ffn)
        return h + expert_ffn(n, ffn, model)

    h = tree["embedding"][tokens]
    for kinds, layer in zip(layer_kinds(model), tree["layers"]):
        h = block(h, layer, kinds)
    return mm(rms(h, tree["out_norm"], eps), tree["head"])


def sequence_loss(tree, tokens, labels, tables, model, q_block):
    """Summed next-token cross-entropy of one sequence."""
    logits = sequence_logits(tree, tokens, tables, model, q_block)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, labels[:, None], axis=-1).sum()


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


@functools.partial(jax.jit, static_argnames=("model_key",))
def _sequence_loss(tree, tokens, labels, tables, model_key):
    with jax.default_matmul_precision("highest"):
        return sequence_loss(tree, tokens, labels, tables,
                             _model(model_key),
                             q_block=min(tokens.shape[0], 512))


@functools.partial(jax.jit, static_argnames=("model_key",))
def _sequence_grads(tree, tokens, labels, tables, model_key):
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(sequence_loss)(
            tree, tokens, labels, tables, _model(model_key),
            q_block=min(tokens.shape[0], 512))


def _batch(batch, model):
    tokens, labels = (numpy.asarray(a, numpy.int32) for a in batch)
    tables = rope_tables(tokens.shape[1], model["head_dim"],
                         model["rope_theta"])
    return tokens, labels, tables


def loss(tree, batch, model):
    """Mean next-token loss of ``batch`` = (tokens, labels), (B, S)
    integer arrays."""
    tokens, labels, tables = _batch(batch, model)
    on_device = jax.device_put(tree)
    total = sum(float(_sequence_loss(on_device, t, l, tables,
                                     _key(model)))
                for t, l in zip(tokens, labels))
    return total / tokens.size


def train(tree, batches, model, lr, moment):
    """Momentum SGD over ``batches`` in order; -> (tree after the last
    step, [loss of each batch before its step])."""
    tree = jax.tree_util.tree_map(
        lambda a: numpy.array(a, numpy.float32), tree)
    velocity = jax.tree_util.tree_map(numpy.zeros_like, tree)
    lr, moment = numpy.float32(lr), numpy.float32(moment)
    losses = []
    spent = {"to the chip": 0.0, "gradients": 0.0, "to the host": 0.0,
             "update on the host": 0.0}

    def clock(what, since):
        spent[what] += time.perf_counter() - since
        return time.perf_counter()

    for batch in batches:
        tokens, labels, tables = _batch(batch, model)
        at = time.perf_counter()
        on_device = jax.block_until_ready(jax.device_put(tree))
        at = clock("to the chip", at)
        total, grads = 0.0, None
        for t, l in zip(tokens, labels):
            value, g = jax.block_until_ready(_sequence_grads(
                on_device, t, l, tables, _key(model)))
            at = clock("gradients", at)
            g = jax.device_get(g)
            at = clock("to the host", at)
            total += float(value)
            if grads is None:
                grads = jax.tree_util.tree_map(numpy.array, g)
            else:
                jax.tree_util.tree_map(
                    lambda a, b: numpy.add(a, b, out=a), grads, g)
            at = clock("update on the host", at)
        del on_device, g
        losses.append(total / tokens.size)
        step = -lr / numpy.float32(tokens.size)

        def update(path, w, v, g):
            if path[-1].key != "expert_bias":   # a buffer: not trained
                v *= moment
                g *= step
                v += g                          # v <- m v - lr g
                w += v
        jax.tree_util.tree_map_with_path(update, tree, velocity, grads)
        clock("update on the host", at)
    print("reference train: %d steps, seconds %s" % (
        len(losses), ", ".join("%s %.1f" % item
                               for item in spent.items())), flush=True)
    return tree, losses
