"""Plain reference of the Laguna decoder
(``configs/laguna_s_2_1.json``).

Written from the layer equations as the configuration file states them,
in ``jax.numpy`` and float32 under
``jax.default_matmul_precision("highest")`` — no kernels, no band of
tiles, no sort, no grouped product, no bf16, nothing imported from the
program. It takes the program's weights (so that both sides compute the
same function) and the benchmark's own statement of the architecture
(the configuration file's ``model``: ``operators[kind]`` holds what a
layer type has of its own — ``heads``, ``rope_theta``, ``rotary_dim``,
``rope_scaling``, ``window``). ``x`` is one sequence, (S, d);
``rms(x; g) = x * rsqrt(mean(x^2) + eps) * g``; no bias anywhere.

    h0     = E[tokens]
    block  : a = h + Op(rms(h; g_op));  h' = a + FFN(rms(a; g_ffn))
    Op     : q = n W_q (H_i heads of dh), k = n W_k, v = n W_v (KV
             heads), gamma = sigmoid(n W_gamma) (one a head and token);
             q, k <- R_i(q), R_i(k);
             query head h reads K/V head h // (H_i / KV);
             A = softmax(q k^T / sqrt(dh) + M_i) v,
             M_i[t, s] = 0 if s <= t and (no window, or t - s < W)
                         else -inf;
             Op = concat_h(gamma_h A_h) W_o
    R_i    : half-split rotation of the first r values of a head, the
             rest as projected: t[:r] <- t[:r] cos + rotate_half(t[:r])
             sin with cos, sin of t * f_j, f_j = theta^(-2j / r),
             j < r / 2. With ``rope_scaling`` (YaRN): c(beta) = r
             ln(original / (2 pi beta)) / (2 ln theta); lo =
             floor(c(beta_fast)), hi = ceil(c(beta_slow)), clamped to
             [0, r - 1]; ramp_j = clip((j - lo) / (hi - lo), 0, 1);
             f_j <- f_j (1 - ramp_j) + f_j / factor ramp_j; cos and sin
             times attention_factor. Tables in float64 on the host.
    FFN    : layer < dense_layers: (silu(n W1) * (n W3)) W2; else
             s = sigmoid(n W_r); selected = top-k of s + b (b = 0);
             p = s[selected] / (sum p + 1e-6) * routed_scaling;
             (silu(n W1_s) * (n W3_s)) W2_s              (shared)
             + sum over selected AND held e of
               p_e * (silu(n W1_e) * (n W3_e)) W2_e
    logits = rms(h_L; g_out) W_head
    loss   = mean over tokens of -log softmax(logits)[next token]

The scores are DENSE and the mask is written on them as above; they are
made ``q_block`` queries and one K/V head's group of query heads at a
time (72 x 8192^2 float32 scores are 19 GB whole): blocks of the same
mathematics — a softmax row is whole inside its block — under
``jax.checkpoint``, so that the backward makes a block again instead of
keeping every block's probabilities. The expert layer is a plain loop
over the held experts, each applied to every token under a mask; what
the absent experts would add is left out, as in the program.

Training steps are momentum SGD, ``v <- m v - lr g; w <- w + v``, on
every parameter but ``b``. Parameters and velocity live on the HOST as
numpy arrays (the program's own weights and momentum, 6.5 GB at the
timed sizes, are still on the chip when the check runs), and the loss
and its gradient walk the chain rule ONE SUB-LAYER AT A TIME
(:func:`stages`), as ``reference/solar_open2.py`` does: a sub-layer's
parameters go up, its jitted function or ``jax.vjp`` runs, its
gradients come down and are applied at once. A test holds the walk to
``jax.grad`` of the whole.

``experiment`` is the seam ``benchmark/tests/chip_grads_laguna.py``
plants its faults through; empty, the functions are the model.
"""

import functools
import json
import time

import jax
import jax.numpy as jnp
import numpy

#: Applied to both operands of every matrix product. The identity: the
#: reference is float32. A precision experiment (PERF.md section 6)
#: puts a rounding here.
round_operand = None

#: Departures from the model, for ``chip_grads_laguna.py`` alone:
#: ``window`` ("none": the sliding layers see the whole triangle; an
#: int: that window instead of the configuration's), ``detach_gates``
#: (no gradient through the per-head gates), ``plain_rope`` (the full
#: layers' tables without YaRN), ``whole_head`` (the full layers rotate
#: the whole head), ``group`` (query heads a K/V head in ``g(h)`` of
#: the sliding layers, clipped to the K/V heads there are).
experiment = {}

#: queries a block of scores holds
Q_BLOCK = 256


def mm(a, b):
    if round_operand is not None:
        a, b = round_operand(a), round_operand(b)
    return a @ b


def ein(spec, a, b):
    if round_operand is not None:
        a, b = round_operand(a), round_operand(b)
    return jnp.einsum(spec, a, b)


def held(model):
    lo, hi = model.get("experts_held") or (0, model["moe_experts"])
    return int(lo), int(hi)


def ffn_kind(model, index):
    return "swiglu_ffn" if index < model["dense_layers"] else "expert_ffn"


def shapes(model):
    """{unit: {parameter: shape}} the configuration states; an
    attention unit under its operator's name."""
    d, v = model["dim"], model["vocab"]
    kv, dh = model["kv_heads"], model["head_dim"]
    f, fe, fs = model["ffn_hidden"], model["moe_hidden"], \
        model["moe_shared_hidden"]
    e = model["moe_experts"]
    lo, hi = held(model)
    sizes = {
        "embedding": {"weights": (v, d)},
        "swiglu_ffn": {"weights": (d, 2 * f), "weights2": (f, d),
                       "norm": (d,)},
        "expert_ffn": {"weights": (d, e),
                       "weights13": (hi - lo, d, 2 * fe),
                       "weights2": (hi - lo, fe, d), "norm": (d,),
                       "expert_bias": (e,), "shared13": (d, 2 * fs),
                       "shared2": (fs, d)},
        "rms_norm": {"weights": (d,)},
        "token_dense": {"weights": (d, v)},
    }
    for kind, own in model["operators"].items():
        h = own["heads"]
        sizes[kind] = {"weights": (d, (h + 2 * kv) * dh + h),
                       "weights_out": (h * dh, d), "norm": (d,)}
    return sizes


def count_parameters(model):
    """Parameters of the configuration as cut, the selection biases
    (a buffer) left out."""
    sizes = shapes(model)

    def of(kind):
        return sum(int(numpy.prod(shape))
                   for name, shape in sizes[kind].items()
                   if name != "expert_bias")

    return of("embedding") + of("rms_norm") + of("token_dense") + sum(
        of(kind) + of(ffn_kind(model, i))
        for i, kind in enumerate(model["layers"]))


def from_program(units, model):
    """``units``: [(kind, {name: array})] of the program's forward
    units in order, as ``export_params()`` gives them; -> the
    reference's parameter tree (numpy, on the host). The shapes are
    checked against the configuration file, so a program that quietly
    trained another width or head count fails here and not in a
    tolerance."""
    mine = [k for i, kind in enumerate(model["layers"])
            for k in (kind, ffn_kind(model, i))]
    want = ["embedding"] + [
        "gqa_attention" if k in model["operators"] else k for k in mine] \
        + ["rms_norm", "token_dense"]
    kinds = [k for k, _ in units]
    if kinds != want:
        raise ValueError("program's layers %r are not the "
                         "configuration's %r" % (kinds, want))
    sizes = shapes(model)
    arrays = []
    for name, (kind, params) in zip(
            ["embedding"] + mine + ["rms_norm", "token_dense"], units):
        got = {k: tuple(a.shape) for k, a in params.items()}
        if got != sizes[name]:
            raise ValueError("%s unit (%s) has %r, the configuration "
                             "says %r" % (kind, name, got, sizes[name]))
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


def rotary_tables(seq, own, head_dim):
    """(cos, sin), (seq, r / 2) float32 each, of the operator ``own``,
    by the formulas in the module's head, in float64."""
    r = own.get("rotary_dim") or head_dim
    theta = float(own["rope_theta"])
    scaling = own.get("rope_scaling")
    if experiment.get("whole_head") and scaling:
        r = head_dim
    j = numpy.arange(r // 2, dtype=numpy.float64)
    inv = theta ** (-2.0 * j / r)
    factor = 1.0
    if scaling and not experiment.get("plain_rope"):
        def c(beta):
            return r * numpy.log(
                scaling["original_max_position_embeddings"]
                / (2 * numpy.pi * beta)) / (2 * numpy.log(theta))
        lo = max(numpy.floor(c(scaling["beta_fast"])), 0)
        hi = min(numpy.ceil(c(scaling["beta_slow"])), r - 1)
        ramp = numpy.clip((j - lo) / max(hi - lo, 1e-3), 0, 1)
        inv = inv * (1 - ramp) + inv / scaling["factor"] * ramp
        factor = scaling["attention_factor"]
    angle = numpy.arange(seq, dtype=numpy.float64)[:, None] * inv[None]
    return ((numpy.cos(angle) * factor).astype(numpy.float32),
            (numpy.sin(angle) * factor).astype(numpy.float32))


def rotate(t, cos, sin):
    """(heads, S, dh): the first ``2 x cos.shape[-1]`` values of each
    head turned, half-split over those; the rest pass."""
    r = 2 * cos.shape[-1]
    a, b, rest = t[..., :r // 2], t[..., r // 2:r], t[..., r:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, rest],
                           -1)


def attention(x, p, own, model, q_block):
    """The operator ``own`` (``model["operators"][kind]``) on
    normalised input (S, d)."""
    s = x.shape[0]
    h, kv, dh = own["heads"], model["kv_heads"], model["head_dim"]
    window = experiment.get("window", own.get("window")) \
        if own.get("window") else None
    group = h // kv
    w_q, w_k, w_v, w_gate = jnp.split(
        p["weights"], [h * dh, (h + kv) * dh, (h + 2 * kv) * dh], axis=1)

    def heads(t, n):
        return t.reshape(s, n, dh).transpose(1, 0, 2)

    cos, sin = rotary_tables(s, own, dh)
    q = rotate(heads(mm(x, w_q), h), cos, sin)
    k = rotate(heads(mm(x, w_k), kv), cos, sin)
    v = heads(mm(x, w_v), kv)
    if own.get("window") and experiment.get("group"):
        # the planted g(h): a wrong group width, the last K/V head
        # taking what is left
        reads = numpy.minimum(numpy.arange(h) // experiment["group"],
                              kv - 1)
        k, v = k[reads][:, None], v[reads][:, None]
        q = q[:, None]
    else:
        q = q.reshape(kv, group, s, dh)
        k, v = k[:, None], v[:, None]
    cols = jnp.arange(s)

    @jax.checkpoint
    def block(q_n, k_n, v_n, start):
        """One K/V head's query heads (g, S, dh), ``q_block`` queries
        from ``start``: dense scores, the mask, the softmax."""
        qb = jax.lax.dynamic_slice_in_dim(q_n, start, q_block, axis=1)
        scores = ein("gqd,kd->gqk", qb, k_n[0]) / numpy.sqrt(dh)
        rows = start + jnp.arange(q_block)
        ahead = rows[:, None] - cols[None, :]        # t - s
        hidden = ahead < 0
        if window not in (None, "none"):
            hidden = hidden | (ahead >= window)
        probs = jax.nn.softmax(jnp.where(hidden, -jnp.inf, scores), -1)
        return ein("gqk,kd->gqd", probs, v_n[0])

    def of_head(qkv):
        q_n, k_n, v_n = qkv
        out = jax.lax.map(lambda start: block(q_n, k_n, v_n, start),
                          jnp.arange(0, s, q_block))
        return out.transpose(1, 0, 2, 3).reshape(q_n.shape)

    out = jax.lax.map(of_head, (q, k, v))            # (n, g, S, dh)
    merged = out.reshape(h, s, dh).transpose(1, 0, 2)
    gamma = jax.nn.sigmoid(mm(x, w_gate))            # (S, h)
    if experiment.get("detach_gates"):
        gamma = jax.lax.stop_gradient(gamma)
    return mm((merged * gamma[:, :, None]).reshape(s, h * dh),
              p["weights_out"])


def swiglu(x, w13, w2):
    h1, h3 = jnp.split(mm(x, w13), 2, axis=-1)
    return mm(jax.nn.silu(h1) * h3, w2)


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
    """The expert layer on normalised input (S, d): the shared expert
    and the part of the routed sum that the held experts give."""
    lo, _ = held(model)
    selected, weight = route(x, p, model)

    @jax.checkpoint
    def one(y, expert):
        w13, w2, index = expert
        mine = jnp.where(selected == index, weight, 0.0).sum(-1)
        return y + mine[:, None] * swiglu(x, w13, w2), None

    ids = lo + jnp.arange(p["weights13"].shape[0])
    y, _ = jax.lax.scan(one, swiglu(x, p["shared13"], p["shared2"]),
                        (p["weights13"], p["weights2"], ids))
    return y


def operator(p, h, kind, model, q_block):
    n = rms(h, p["norm"], model["norm_eps"])
    return h + attention(n, p, model["operators"][kind], model, q_block)


def feed_forward(p, h, kind, model):
    n = rms(h, p["norm"], model["norm_eps"])
    if kind == "swiglu_ffn":
        return h + swiglu(n, p["weights"], p["weights2"])
    return h + expert_ffn(n, p, model)


def exit_loss(p, h, labels, model):
    """Summed next-token cross-entropy of one sequence's last state."""
    logits = mm(rms(h, p["out_norm"], model["norm_eps"]), p["head"])
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, labels[:, None], axis=-1).sum()


def q_block_of(seq):
    return Q_BLOCK if seq % Q_BLOCK == 0 else seq


def sequence_loss(tree, tokens, labels, model, q_block=None):
    """The loss above, summed over one sequence, as ONE function (the
    tests' ``jax.grad`` of the whole; the timed sizes walk
    :func:`stages`)."""
    q_block = q_block or q_block_of(len(tokens))
    h = tree["embedding"][tokens]
    for i, (kind, layer) in enumerate(zip(model["layers"],
                                          tree["layers"])):
        h = operator(layer["op"], h, kind, model, q_block)
        h = feed_forward(layer["ffn"], h, ffn_kind(model, i), model)
    return exit_loss({k: tree[k] for k in ("out_norm", "head")}, h,
                     labels, model)


# -- loss and training, one sub-layer on the device at a time --------------


def _key(model):
    """The model's shape as a hashable static argument."""
    return json.dumps(model, sort_keys=True)


@functools.lru_cache(maxsize=None)
def _compiled(model_key, seq, rounding, planted):
    """The jitted pieces :func:`stages` names, each with its pullback.
    ``rounding`` and ``planted`` are ``round_operand`` and
    ``experiment`` at the time: keys, since the pieces read them when
    they are traced."""
    model = json.loads(model_key)
    q_block = q_block_of(seq)

    def precise(fn):
        def run(*args):
            with jax.default_matmul_precision("highest"):
                return fn(*args)
        return jax.jit(run)

    def pullback(fn):
        """(cotangent, parameters, input) -> the cotangents of the
        parameters and the input."""
        def back(cotangent, p, x):
            return jax.vjp(fn, p, x)[1](cotangent)
        return precise(back)

    pieces = {kind: functools.partial(operator, kind=kind, model=model,
                                      q_block=q_block)
              for kind in model["operators"]}
    for kind in ("swiglu_ffn", "expert_ffn"):
        pieces[kind] = functools.partial(feed_forward, kind=kind,
                                         model=model)
    out = {name: (precise(fn), pullback(fn))
           for name, fn in pieces.items()}
    out["exit"] = precise(jax.value_and_grad(
        functools.partial(exit_loss, model=model), argnums=(0, 1)))
    out["exit_value"] = precise(functools.partial(exit_loss, model=model))
    return out


def compiled(model, seq):
    return _compiled(_key(model), seq, round_operand,
                     tuple(sorted(experiment.items())))


def stages(tree, model):
    """[(piece name, the sub-layer's parameters on the host)] from the
    embedding's output to the last layer's."""
    return [stage for i, (kind, layer) in enumerate(
                zip(model["layers"], tree["layers"]))
            for stage in ((kind, layer["op"]),
                          (ffn_kind(model, i), layer["ffn"]))]


def _exit_params(tree):
    return {k: tree[k] for k in ("out_norm", "head")}


def sequence_value(tree, tokens, labels, model):
    """:func:`sequence_loss` of a tree on the host."""
    piece = compiled(model, len(tokens))
    h = jnp.asarray(tree["embedding"][tokens])
    for name, p in stages(tree, model):
        h = piece[name][0](p, h)
    return float(piece["exit_value"](_exit_params(tree), h, labels))


def sequence_gradients(tree, tokens, labels, model, sink):
    """:func:`sequence_loss` and its gradient for a tree on the host:
    ``sink(parameters on the host, their gradient)`` is called once for
    every sub-layer, the exit and the embedding (``jax.grad``'s result,
    a test holds it to that), last layer first; it may change the
    parameters in place, nothing reads them again. -> the loss."""
    piece = compiled(model, len(tokens))
    walk = stages(tree, model)
    h = jnp.asarray(tree["embedding"][tokens])
    inputs = []
    for name, p in walk:
        inputs.append(h)
        h = piece[name][0](p, h)
    last = _exit_params(tree)
    value, (dlast, dh) = piece["exit"](last, h, labels)
    value = float(value)
    sink(last, jax.device_get(dlast))
    del dlast
    for name, p in reversed(walk):
        dp, dh = piece[name][1](dh, p, inputs.pop())
        sink(p, jax.device_get(dp))
        del dp
    dembedding = numpy.zeros_like(tree["embedding"])
    numpy.add.at(dembedding, numpy.asarray(tokens), numpy.asarray(dh))
    sink({"embedding": tree["embedding"]}, {"embedding": dembedding})
    return value


def _batch(batch):
    return tuple(numpy.asarray(a, numpy.int32) for a in batch)


def loss(tree, batch, model):
    """Mean next-token loss of ``batch`` = (tokens, labels), (B, S)
    integer arrays."""
    tokens, labels = _batch(batch)
    return sum(sequence_value(tree, t, l, model)
               for t, l in zip(tokens, labels)) / tokens.size


def gradients(tree, batch, model):
    """(mean loss, its gradient tree as numpy arrays on the host): for
    tests and ``chip_grads_laguna.py``; :func:`train` never holds a
    whole gradient."""
    tokens, labels = _batch(batch)
    grads = jax.tree_util.tree_map(numpy.zeros_like, tree)
    where = {id(leaf): g for leaf, g in zip(
        jax.tree_util.tree_leaves(tree), jax.tree_util.tree_leaves(grads))}

    def sink(p, g):
        for name, leaf in p.items():
            where[id(leaf)] += g[name]

    total = sum(sequence_gradients(tree, t, l, model, sink)
                for t, l in zip(tokens, labels))
    scale = numpy.float32(1.0 / tokens.size)
    jax.tree_util.tree_map(lambda a: numpy.multiply(a, scale, out=a),
                           grads)
    return total / tokens.size, grads


def train(tree, batches, model, lr, moment):
    """Momentum SGD over ``batches`` of ONE sequence in order; ->
    (tree after the last step, [loss of each batch before its step]).
    A sub-layer's step is taken as its gradient comes down."""
    tree = jax.tree_util.tree_map(
        lambda a: numpy.array(a, numpy.float32), tree)
    velocity = {id(leaf): numpy.zeros_like(leaf)
                for leaf in jax.tree_util.tree_leaves(tree)}
    lr, moment = numpy.float32(lr), numpy.float32(moment)
    losses = []
    start = time.perf_counter()
    for batch in batches:
        tokens, labels = _batch(batch)
        if len(tokens) != 1:
            raise ValueError("the reference steps a sub-layer as its "
                             "gradient arrives: one sequence a batch, "
                             "got %d" % len(tokens))
        step = -lr / numpy.float32(tokens.size)

        def sink(p, g):
            for name, w in p.items():
                if name == "expert_bias":       # a buffer: not trained
                    continue
                v, dw = velocity[id(w)], numpy.asarray(g[name])
                v *= moment
                v += step * dw                  # v <- m v - lr g
                w += v

        losses.append(sequence_gradients(
            tree, tokens[0], labels[0], model, sink) / tokens.size)
    print("reference train: %d steps, %.1f s" % (
        len(losses), time.perf_counter() - start), flush=True)
    return tree, losses
