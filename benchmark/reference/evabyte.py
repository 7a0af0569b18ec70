"""Plain reference of the EVA byte LM (``configs/evabyte.json``).

Written from the layer equations as the configuration file states them,
in ``jax.numpy`` and float32 under
``jax.default_matmul_precision("highest")`` — no kernels, no folding of
windows into rows, no merge of partial softmaxes, no bf16, nothing
imported from the program. It takes the program's weights (so that both
sides compute the same function) and the benchmark's own statement of
the architecture (the configuration file's ``model``). ``x`` is one
sequence, (S, d); ``rms(x; g) = x * rsqrt(mean(x^2) + eps) * g``; no
bias anywhere; heads h of size dh, window W, chunk C.

    h0     = E[tokens]
    block  : a = h + Eva(rms(h; g_att));
             h' = a + (silu(n W1) * (n W3)) W2,  n = rms(a; g_ffn)
    Eva    : q = R(n W_q), k = R(n W_k), v = n W_v (h heads of dh);
             R the half-split rotation of the whole head, t cos +
             rotate_half(t) sin, angles t * theta^(-2j / dh) (float64
             tables on the host)
             k^_c = sum_{m in c} softmax_m(mu_h . k_m) k_m,
             v^_c = sum_{m in c} softmax_m(phi_h . k_m) v_m,
               c = [cC, (c + 1) C) (a last part-chunk is never seen);
             for query n of window w = n // W:
               L(n) = {m : wW <= m <= n},  R(n) = {c : (c + 1) C <= wW},
               o_n = softmax over [q.k_m / sqrt(dh), m in L(n) |
                     q.k^_c / sqrt(dh), c in R(n)] of [v_m | v^_c];
             Eva = concat_h(o) W_o
    logits = rms(h_L; g_out) W_head, head j the columns [jV, (j + 1) V)
    loss   = 1/heads sum_j mean over t < S - j of
             -log softmax(logits_j[t])[token t + 1 + j]

The scores are DENSE and the mask is written on them as above: a block
of ``Q_BLOCK`` queries and a group of ``HEAD_GROUP`` heads at a time,
each against the keys of the span that can hold its window (the W +
Q_BLOCK keys before the block's end) and against every summary, under
the mask of the definition, in ONE softmax; each block under
``jax.checkpoint``, so that the backward makes a block again instead of
keeping every block's probabilities (32 heads x 16384 x 17408 float32
scores would be 36 GB). The pooling softmaxes are over a chunk's C
keys. Departures from the definition: none; what the configuration does
not pin down is its ``assumed`` (the pooling vectors, the summaries of
ROTATED keys, no log C on a summary's score, block-aligned windows,
linear heads, the loss as the heads' mean).

Training steps are momentum SGD, ``v <- m v - lr g; w <- w + v``, on
every parameter. Parameters and velocity live on the HOST as numpy
arrays (the program's own weights and momentum, 6.6 GB at the timed
sizes, are still on the chip when the check runs), and the loss and its
gradient walk the chain rule ONE SUB-LAYER AT A TIME (:func:`stages`),
as ``reference/solar_open2.py`` does: a sub-layer's parameters go up,
its jitted function or ``jax.vjp`` runs, its gradients come down and
are applied at once. A test holds the walk to ``jax.grad`` of the
whole.

``experiment`` is the seam ``benchmark/tests/chip_grads_evabyte.py``
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

#: Departures from the model, for ``chip_grads_evabyte.py`` alone:
#: ``no_remote`` (the summaries dropped: local windows alone),
#: ``mean_pool`` (summaries pooled by the chunk's mean, not by mu /
#: phi), ``window`` (another window), ``chunk`` (another chunk),
#: ``pred_heads`` (the loss over that many of the heads),
#: ``stream_bf16`` (the stream between sub-layers and the logits
#: rounded to bf16, where the configuration keeps them in float32).
experiment = {}

#: queries a block of scores holds, and heads a group
Q_BLOCK = 256
HEAD_GROUP = 8


def mm(a, b):
    if round_operand is not None:
        a, b = round_operand(a), round_operand(b)
    return a @ b


def ein(spec, a, b):
    if round_operand is not None:
        a, b = round_operand(a), round_operand(b)
    return jnp.einsum(spec, a, b)


def shapes(model):
    """{unit kind: {parameter: shape}} the configuration states."""
    d, v, f = model["dim"], model["vocab"], model["ffn_hidden"]
    h, dh = model["heads"], model["head_dim"]
    return {
        "embedding": {"weights": (v, d)},
        "eva_attention": {"weights": (d, 3 * h * dh),
                          "weights_out": (h * dh, d), "norm": (d,),
                          "pool_key": (h, dh), "pool_value": (h, dh)},
        "swiglu_ffn": {"weights": (d, 2 * f), "weights2": (f, d),
                       "norm": (d,)},
        "rms_norm": {"weights": (d,)},
        "token_dense": {"weights": (d, model["pred_heads"] * v)},
    }


def count_parameters(model):
    """Parameters of the configuration as cut."""
    sizes = shapes(model)

    def of(kind):
        return sum(int(numpy.prod(shape)) for shape in sizes[kind].values())

    return of("embedding") + of("rms_norm") + of("token_dense") \
        + len(model["layers"]) * (of("eva_attention") + of("swiglu_ffn"))


def from_program(units, model):
    """``units``: [(kind, {name: array})] of the program's forward
    units in order, as ``export_params()`` gives them; -> the
    reference's parameter tree (numpy, on the host). The shapes are
    checked against the configuration file, so a program that quietly
    trained another width, head count or number of heads fails here
    and not in a tolerance."""
    want = ["embedding"] + ["eva_attention", "swiglu_ffn"] \
        * len(model["layers"]) + ["rms_norm", "token_dense"]
    kinds = [k for k, _ in units]
    if kinds != want:
        raise ValueError("program's layers %r are not the "
                         "configuration's %r" % (kinds, want))
    sizes = shapes(model)
    arrays = []
    for kind, params in units:
        got = {k: tuple(a.shape) for k, a in params.items()}
        if got != sizes[kind]:
            raise ValueError("%s unit has %r, the configuration says %r"
                             % (kind, got, sizes[kind]))
        arrays.append({k: numpy.asarray(a, numpy.float32)
                       for k, a in params.items()})
    body = arrays[1:-2]
    return {"embedding": arrays[0]["weights"],
            "layers": [{"op": body[2 * i], "ffn": body[2 * i + 1]}
                       for i in range(len(body) // 2)],
            "out_norm": arrays[-2]["weights"],
            "head": arrays[-1]["weights"]}


# -- the layers ------------------------------------------------------------


def stream(t):
    """The stream between sub-layers, or the logits, as kept: float32,
    or rounded to bf16 under ``experiment["stream_bf16"]``."""
    if experiment.get("stream_bf16"):
        return t.astype(jnp.bfloat16).astype(t.dtype)
    return t


def rms(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * gain


def rotary_tables(seq, head_dim, theta):
    """(cos, sin), (seq, dh / 2) float32 each, made in float64."""
    j = numpy.arange(head_dim // 2, dtype=numpy.float64)
    inv = float(theta) ** (-2.0 * j / head_dim)
    angle = numpy.arange(seq, dtype=numpy.float64)[:, None] * inv[None]
    return (numpy.cos(angle).astype(numpy.float32),
            numpy.sin(angle).astype(numpy.float32))


def rotate(t, cos, sin):
    """(heads, S, dh), half-split over the whole head."""
    a, b = jnp.split(t, 2, axis=-1)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def window_chunk(model):
    return (int(experiment.get("window", model["eva_window"])),
            int(experiment.get("chunk", model["eva_chunk"])))


def summaries(k, v, p, chunk):
    """(k^, v^), (heads, ceil(S / C), dh): each chunk's keys and values
    pooled by a softmax over its keys' scores against mu (k^) and phi
    (v^); a last part-chunk is pooled over its keys alone."""
    h, s, dh = k.shape
    n = -(-s // chunk)
    pad = n * chunk - s

    def chunks(t):
        return jnp.pad(t, ((0, 0), (0, pad), (0, 0))).reshape(
            h, n, chunk, dh)

    kc, vc = chunks(k), chunks(v)
    real = (jnp.arange(n * chunk) < s).reshape(n, chunk)

    def pooled(vector, values):
        if experiment.get("mean_pool"):
            scores = jnp.zeros(kc.shape[:3])
        else:
            scores = (kc * vector[:, None, None, :]).sum(-1)
        weight = jax.nn.softmax(jnp.where(real, scores, -jnp.inf), -1)
        return (weight[..., None] * values).sum(-2)

    return pooled(p["pool_key"], kc), pooled(p["pool_value"], vc)


def attention(x, p, model):
    """EVA on normalised input (S, d)."""
    s = x.shape[0]
    h, dh = model["heads"], model["head_dim"]
    window, chunk = window_chunk(model)
    w_q, w_k, w_v = jnp.split(p["weights"], 3, axis=1)

    def heads(t):
        return t.reshape(s, h, dh).transpose(1, 0, 2)

    cos, sin = rotary_tables(s, dh, model["rope_theta"])
    q = rotate(heads(mm(x, w_q)), cos, sin)
    k = rotate(heads(mm(x, w_k)), cos, sin)
    v = heads(mm(x, w_v))
    kh, vh = summaries(k, v, p, chunk)
    q_block = Q_BLOCK if s % Q_BLOCK == 0 else s
    span = min(window + q_block, s)
    n_sum = kh.shape[1]
    ends = (jnp.arange(n_sum) + 1) * chunk          # (c + 1) C

    @jax.checkpoint
    def block(qg, kg, vg, khg, vhg, start):
        """A group of heads (g, S, dh), ``q_block`` queries from
        ``start``: dense scores against the span that holds their
        windows and every summary, the mask, ONE softmax."""
        qb = jax.lax.dynamic_slice_in_dim(qg, start, q_block, axis=1)
        first = jnp.clip(start + q_block - span, 0, s - span)
        kb = jax.lax.dynamic_slice_in_dim(kg, first, span, axis=1)
        vb = jax.lax.dynamic_slice_in_dim(vg, first, span, axis=1)
        rows = start + jnp.arange(q_block)                  # n
        cols = first + jnp.arange(span)                     # m
        begin = rows // window * window                     # wW
        local = (cols[None, :] >= begin[:, None]) \
            & (cols[None, :] <= rows[:, None])
        remote = ends[None, :] <= begin[:, None]
        if experiment.get("no_remote"):
            remote = remote & False
        scores = jnp.concatenate(
            [ein("gqd,gkd->gqk", qb, kb), ein("gqd,gcd->gqc", qb, khg)],
            -1) / numpy.sqrt(dh)
        seen = jnp.concatenate([local, remote], -1)
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1)
        return ein("gqk,gkd->gqd", probs[..., :span], vb) \
            + ein("gqc,gcd->gqd", probs[..., span:], vhg)

    def of_group(t):
        qg, kg, vg, khg, vhg = t
        out = jax.lax.map(lambda start: block(qg, kg, vg, khg, vhg, start),
                          jnp.arange(0, s, q_block))
        return out.transpose(1, 0, 2, 3).reshape(qg.shape)

    group = HEAD_GROUP if h % HEAD_GROUP == 0 else h

    def grouped(t):
        return t.reshape((h // group, group) + t.shape[1:])

    out = jax.lax.map(of_group, tuple(map(grouped, (q, k, v, kh, vh))))
    merged = out.reshape(h, s, dh).transpose(1, 0, 2).reshape(s, h * dh)
    return mm(merged, p["weights_out"])


def operator(p, h, model):
    h = stream(h)
    return stream(h + attention(rms(h, p["norm"], model["norm_eps"]), p,
                                model))


def feed_forward(p, h, model):
    n = rms(h, p["norm"], model["norm_eps"])
    h1, h3 = jnp.split(mm(n, p["weights"]), 2, axis=-1)
    return stream(h + mm(jax.nn.silu(h1) * h3, p["weights2"]))


def exit_loss(p, h, labels, model):
    """One sequence's loss: the heads' mean of each head's mean cross
    entropy over the positions that have its target."""
    s, vocab = h.shape[0], model["vocab"]
    logits = stream(mm(rms(h, p["out_norm"], model["norm_eps"]),
                       p["head"]))
    logp = jax.nn.log_softmax(
        logits.reshape(s, model["pred_heads"], vocab), axis=-1)
    used = int(experiment.get("pred_heads", model["pred_heads"]))
    total = 0.0
    for j in range(used):
        picked = jnp.take_along_axis(logp[:s - j, j], labels[j:, None], -1)
        total = total - picked.mean()
    return total / used


def sequence_loss(tree, tokens, labels, model):
    """The loss above of one sequence as ONE function (the tests'
    ``jax.grad`` of the whole; the timed sizes walk :func:`stages`)."""
    h = tree["embedding"][tokens]
    for layer in tree["layers"]:
        h = feed_forward(layer["ffn"], operator(layer["op"], h, model),
                         model)
    return exit_loss({k: tree[k] for k in ("out_norm", "head")}, h,
                     labels, model)


# -- loss and training, one sub-layer on the device at a time --------------


def _key(model):
    """The model's shape as a hashable static argument."""
    return json.dumps(model, sort_keys=True)


@functools.lru_cache(maxsize=None)
def _compiled(model_key, rounding, planted):
    """The jitted pieces :func:`stages` names, each with its pullback.
    ``rounding`` and ``planted`` are ``round_operand`` and
    ``experiment`` at the time: keys, since the pieces read them when
    they are traced."""
    model = json.loads(model_key)

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

    pieces = {"eva_attention": functools.partial(operator, model=model),
              "swiglu_ffn": functools.partial(feed_forward, model=model)}
    out = {name: (precise(fn), pullback(fn))
           for name, fn in pieces.items()}
    out["exit"] = precise(jax.value_and_grad(
        functools.partial(exit_loss, model=model), argnums=(0, 1)))
    out["exit_value"] = precise(functools.partial(exit_loss, model=model))
    return out


def compiled(model):
    return _compiled(_key(model), round_operand,
                     tuple(sorted(experiment.items())))


def stages(tree):
    """[(piece name, the sub-layer's parameters on the host)] from the
    embedding's output to the last layer's."""
    return [stage for layer in tree["layers"]
            for stage in (("eva_attention", layer["op"]),
                          ("swiglu_ffn", layer["ffn"]))]


def _exit_params(tree):
    return {k: tree[k] for k in ("out_norm", "head")}


def sequence_value(tree, tokens, labels, model):
    """:func:`sequence_loss` of a tree on the host."""
    piece = compiled(model)
    h = jnp.asarray(tree["embedding"][tokens])
    for name, p in stages(tree):
        h = piece[name][0](p, h)
    return float(piece["exit_value"](_exit_params(tree), h, labels))


def sequence_gradients(tree, tokens, labels, model, sink):
    """:func:`sequence_loss` and its gradient for a tree on the host:
    ``sink(parameters on the host, their gradient)`` is called once for
    every sub-layer, the exit and the embedding (``jax.grad``'s result,
    a test holds it to that), last layer first; it may change the
    parameters in place, nothing reads them again. -> the loss."""
    piece = compiled(model)
    walk = stages(tree)
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
    """Mean loss of ``batch`` = (tokens, labels), (B, S) integer arrays:
    every sequence has the same positions, so the mean of the
    sequences' losses."""
    tokens, labels = _batch(batch)
    return sum(sequence_value(tree, t, l, model)
               for t, l in zip(tokens, labels)) / len(tokens)


def gradients(tree, batch, model):
    """(mean loss, its gradient tree as numpy arrays on the host): for
    tests and ``chip_grads_evabyte.py``; :func:`train` never holds a
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
    scale = numpy.float32(1.0 / len(tokens))
    jax.tree_util.tree_map(lambda a: numpy.multiply(a, scale, out=a),
                           grads)
    return total / len(tokens), grads


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

        def sink(p, g):
            for name, w in p.items():
                v, dw = velocity[id(w)], numpy.asarray(g[name])
                v *= moment
                v -= lr * dw                    # v <- m v - lr g
                w += v

        losses.append(sequence_gradients(
            tree, tokens[0], labels[0], model, sink))
    print("reference train: %d steps, %.1f s" % (
        len(losses), time.perf_counter() - start), flush=True)
    return tree, losses
