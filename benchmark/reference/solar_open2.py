"""Plain reference of the Solar-Open2 decoder
(``configs/solar_open2_250b.json``).

Written from the layer equations as the configuration file states them,
in ``jax.numpy`` and float32 under
``jax.default_matmul_precision("highest")`` — no kernels, no chunks, no
sort, no grouped product, no bf16, nothing imported from the program.
It takes the program's weights (so that both sides compute the same
function) and the benchmark's own statement of the architecture (the
configuration file's ``model``). ``x`` is one sequence, (S, d);
``rms(x; g) = x * rsqrt(mean(x^2) + eps) * g``; no bias but ``c_g``.

    h0     = E[tokens]                           (no positions anywhere)
    block  : a = h + Op(rms(h; g_op));  h' = a + FFN(rms(a; g_ffn))
    delta  : q, k, v = silu(conv(n W_q)), silu(conv(n W_k)),
             silu(conv(n W_v)): H heads of dk; conv depthwise, causal,
             c_t = sum_j w[:, j] * u_{t-(L-1)+j}  (zeros before t = 0);
             q, k <- q / |q| * dk^-1/2, k / |k| per head and token,
             |t| = sqrt(sum t^2 + 1e-6);
             a_t = -exp(A_log_h) * softplus((n_t W_f1) W_f2 + dt_bias),
             b_t = 2 sigmoid(n_t W_b);  per head, S_0 = 0, token by
             token:
             S_t = (I - b_t k_t k_t^T) diag(exp(a_t)) S_{t-1}
                   + b_t k_t v_t^T;    o_t = S_t^T q_t;
             Op = (rms(o_t; g_o) per head
                   * sigmoid((n_t W_g1) W_g2 + c_g)) W_o
    attn   : q = n W_q (H heads), k = n W_k, v = n W_v (KV heads), NO
             rotation, no q/k norm; query head i reads K/V head
             i // (H / KV);
             Op = (merge(softmax(q k^T / sqrt(dh) + causal) v)
                   * sigmoid(n W_gate)) W_o
    expert : s = sigmoid(n W_r); selected = top-k of s + b (b = 0);
             p = s[selected] / (sum p + 1e-6) * scaling;
             FFN = (silu(n W1_s) * (n W3_s)) W2_s            (shared)
                   + sum over selected AND held e of
                     p_e * (silu(n W1_e) * (n W3_e)) W2_e
    logits = rms(h_L; g_out) W_head
    loss   = mean over tokens of -log softmax(logits)[next token]

The recurrence is a ``lax.scan`` over the tokens, exactly the two lines
above; a ``jax.checkpoint`` around every ``BLOCK`` tokens changes no
mathematics (an unchecked 4,096-step scan would keep a state of
``H x dk x dv`` float32 a token, 17 GB a layer). The expert layer is a
plain loop over the held experts, each applied to every token under a
mask; what the absent experts would add is left out, as in the program.

Training steps are momentum SGD, ``v <- m v - lr g; w <- w + v``, on
every parameter but ``b``. Parameters and velocity live on the HOST as
numpy arrays: the program's own weights and momentum (8 bytes a
parameter, 10.4 GB at the timed sizes) are still on the chip when the
check runs, and neither a second float32 model nor its gradient fits
beside them. So the loss and its gradient walk the chain rule ONE
SUB-LAYER AT A TIME (:func:`stages`): a sub-layer's parameters go up,
its jitted function or ``jax.vjp`` runs, its gradients come down and
are applied at once; the sub-layers' inputs wait on the device (32 MB
each at S = 4096). A test holds the walk to ``jax.grad`` of the whole.

``experiment`` is the seam ``benchmark/tests/chip_grads_solar.py``
plants its faults through; empty, the functions are the model.
"""

import functools
import time

import jax
import jax.numpy as jnp
import numpy

#: tokens between two checkpoints of the recurrence
BLOCK = 64

#: Applied to both operands of every matrix product. The identity: the
#: reference is float32. A precision experiment (PERF.md section 6)
#: puts a rounding here.
round_operand = None

#: Departures from the model, for ``chip_grads_solar.py`` alone:
#: ``beta_scale`` (2: the factor of ``b``), ``no_decay`` (``a = 0``),
#: ``state_dtype`` (the recurrence's carry rounded to it every token),
#: ``detach_gates`` (no gradient through either output gate).
experiment = {}


def mm(a, b):
    if round_operand is not None:
        a, b = round_operand(a), round_operand(b)
    return a @ b


def ein(spec, a, b):
    if round_operand is not None:
        a, b = round_operand(a), round_operand(b)
    return jnp.einsum(spec, a, b)


OPERATORS = {"gated_nope_attention": "gqa_attention",
             "delta_attention": "delta_attention"}


def held(model):
    lo, hi = model.get("experts_held") or (0, model["moe_experts"])
    return int(lo), int(hi)


def shapes(model):
    """{unit kind: {parameter: shape}} the configuration states."""
    d, v = model["dim"], model["vocab"]
    h, kv, dh = model["heads"], model["kv_heads"], model["head_dim"]
    dn, dk = model["delta_heads"], model["delta_head_dim"]
    rank, taps = model["delta_gate_rank"], model["delta_conv_kernel"]
    fe, fs, e = model["moe_hidden"], model["moe_shared_hidden"], \
        model["moe_experts"]
    lo, hi = held(model)
    wide = dn * dk
    return {
        "embedding": {"weights": (v, d)},
        "delta_attention": {
            "weights": (d, 3 * wide), "conv": (3 * wide, taps),
            "weights_decay_in": (d, rank),
            "weights_decay_out": (rank, wide), "weights_beta": (d, dn),
            "a_log": (dn,), "dt_bias": (wide,),
            "weights_gate_in": (d, rank),
            "weights_gate_out": (rank, wide), "gate_bias": (wide,),
            "weights_out": (wide, d), "norm": (d,), "norm_out": (dk,)},
        "gqa_attention": {"weights": (d, (2 * h + 2 * kv) * dh),
                          "weights_out": (h * dh, d), "norm": (d,)},
        "expert_ffn": {"weights": (d, e),
                       "weights13": (hi - lo, d, 2 * fe),
                       "weights2": (hi - lo, fe, d), "norm": (d,),
                       "expert_bias": (e,), "shared13": (d, 2 * fs),
                       "shared2": (fs, d)},
        "rms_norm": {"weights": (d,)},
        "token_dense": {"weights": (d, v)},
    }


def count_parameters(model):
    """Parameters of the configuration as cut, the selection biases
    (a buffer) left out."""
    sizes = shapes(model)

    def of(kind):
        return sum(int(numpy.prod(shape))
                   for name, shape in sizes[kind].items()
                   if name != "expert_bias")

    return of("embedding") + of("rms_norm") + of("token_dense") + sum(
        of(OPERATORS[kind]) + of("expert_ffn")
        for kind in model["layers"])


def from_program(units, model):
    """``units``: [(kind, {name: array})] of the program's forward
    units in order, as ``export_params()`` gives them; -> the
    reference's parameter tree (numpy, on the host). The shapes are
    checked against the configuration file, so a program that quietly
    trained another width fails here and not in a tolerance."""
    want = ["embedding"] + [k for kind in model["layers"]
                            for k in (OPERATORS[kind], "expert_ffn")] \
        + ["rms_norm", "token_dense"]
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


def rms(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * gain


def causal_taps(u, w):
    """``c_t = sum_j w[:, j] u_{t-(L-1)+j}`` over (S, n), zeros before
    the sequence."""
    taps = w.shape[1]
    c = jnp.zeros_like(u)
    for j in range(taps):
        shift = taps - 1 - j            # tap j reads u_{t - shift}
        moved = u if shift == 0 else jnp.concatenate(
            [jnp.zeros_like(u[:shift]), u[:-shift]], axis=0)
        c = c + w[:, j] * moved
    return c


def delta_recurrence(q, k, v, a, b):
    """``o`` (S, H, dv) of the recurrence above, token by token:
    ``q, k, a`` (S, H, dk), ``v`` (S, H, dv), ``b`` (S, H);
    -> (o, the final state (H, dk, dv))."""
    carried = experiment.get("state_dtype")

    def token(state, x):
        q_t, k_t, v_t, a_t, b_t = x
        state = jnp.exp(a_t)[:, :, None] * state
        read = ein("hkv,hk->hv", state, k_t)
        state = state + (b_t[:, None] * k_t)[:, :, None] \
            * (v_t - read)[:, None, :]
        if carried is not None:
            state = state.astype(carried).astype(jnp.float32)
        return state, ein("hkv,hk->hv", state, q_t)

    @jax.checkpoint
    def block(state, xs):
        return jax.lax.scan(token, state, xs)

    s, h, dk = q.shape
    n = s // BLOCK if s % BLOCK == 0 else 1
    xs = tuple(t.reshape((n, s // n) + t.shape[1:])
               for t in (q, k, v, a, b))
    state, o = jax.lax.scan(
        block, jnp.zeros((h, dk, v.shape[-1]), jnp.float32), xs)
    return o.reshape(s, h, -1), state


def unit_length(t):
    return t * jax.lax.rsqrt((t * t).sum(-1, keepdims=True) + 1e-6)


def output_gate(pre):
    gate = jax.nn.sigmoid(pre)
    return jax.lax.stop_gradient(gate) \
        if experiment.get("detach_gates") else gate


def delta_attention(x, p, model):
    """The operator of a ``delta_attention`` layer on normalised input
    (S, d)."""
    s = x.shape[0]
    h, dk = model["delta_heads"], model["delta_head_dim"]
    eps = model["norm_eps"]
    q, k, v = (jax.nn.silu(causal_taps(mm(x, w), taps)).reshape(s, h, dk)
               for w, taps in zip(jnp.split(p["weights"], 3, axis=1),
                                  jnp.split(p["conv"], 3, axis=0)))
    q, k = unit_length(q) * dk ** -0.5, unit_length(k)
    a = -jnp.exp(p["a_log"])[:, None] * jax.nn.softplus(
        (mm(mm(x, p["weights_decay_in"]), p["weights_decay_out"])
         + p["dt_bias"]).reshape(s, h, dk))
    if experiment.get("no_decay"):
        a = jnp.zeros_like(a)
    b = experiment.get("beta_scale", 2.0) \
        * jax.nn.sigmoid(mm(x, p["weights_beta"]))
    o, _ = delta_recurrence(q, k, v, a, b)
    gate = output_gate(
        mm(mm(x, p["weights_gate_in"]), p["weights_gate_out"])
        + p["gate_bias"])
    o = rms(o, p["norm_out"], eps).reshape(s, h * dk)
    return mm(o * gate, p["weights_out"])


def gated_attention(x, p, model, q_block):
    """The operator of a ``gated_nope_attention`` layer on normalised
    input (S, d); the queries are taken ``q_block`` at a time so that
    the score matrix is (H, q_block, S)."""
    s = x.shape[0]
    h, kv, dh = model["heads"], model["kv_heads"], model["head_dim"]
    w_q, w_k, w_v, w_gate = jnp.split(
        p["weights"], [h * dh, (h + kv) * dh, (h + 2 * kv) * dh], axis=1)

    def heads(t, n):
        return t.reshape(s, n, dh).transpose(1, 0, 2)

    q = heads(mm(x, w_q), h).reshape(kv, h // kv, s, dh)
    k, v = heads(mm(x, w_k), kv), heads(mm(x, w_v), kv)
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
    return mm(merged * output_gate(mm(x, w_gate)), p["weights_out"])


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
    if kind == "delta_attention":
        return h + delta_attention(n, p, model)
    return h + gated_attention(n, p, model, q_block)


def expert_layer(p, h, model):
    return h + expert_ffn(rms(h, p["norm"], model["norm_eps"]), p, model)


def exit_loss(p, h, labels, model):
    """Summed next-token cross-entropy of one sequence's last state."""
    logits = mm(rms(h, p["out_norm"], model["norm_eps"]), p["head"])
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, labels[:, None], axis=-1).sum()


def sequence_loss(tree, tokens, labels, model, q_block):
    """The loss above, summed over one sequence, as ONE function (the
    tests' ``jax.grad`` of the whole; the timed sizes walk
    :func:`stages`)."""
    h = tree["embedding"][tokens]
    for kind, layer in zip(model["layers"], tree["layers"]):
        h = operator(layer["op"], h, kind, model, q_block)
        h = expert_layer(layer["ffn"], h, model)
    return exit_loss({k: tree[k] for k in ("out_norm", "head")}, h,
                     labels, model)


# -- loss and training, one sub-layer on the device at a time --------------


def _model(key):
    return {k: (list(v) if isinstance(v, tuple) else v) for k, v in key}


def _key(model):
    """The model's shape as a hashable static argument."""
    def frozen(v):
        return tuple(v) if isinstance(v, list) else v
    return tuple(sorted(
        (k, frozen(v)) for k, v in model.items()
        if isinstance(v, (int, float, str, list))))


@functools.lru_cache(maxsize=None)
def _compiled(model_key, seq, rounding, planted):
    """The jitted pieces :func:`stages` names, each with its pullback.
    ``rounding`` and ``planted`` are ``round_operand`` and
    ``experiment`` at the time: keys, since the pieces read them when
    they are traced."""
    model = _model(model_key)
    q_block = min(seq, 512)

    def precise(fn):
        def run(*args):
            with jax.default_matmul_precision("highest"):
                return fn(*args)
        return jax.jit(run)

    def pullback(fn):
        """(cotangent, parameters, input, *rest) -> the cotangents of
        the parameters and the input."""
        def back(cotangent, p, x, *rest):
            return jax.vjp(lambda p, x: fn(p, x, *rest), p, x)[1](
                cotangent)
        return precise(back)

    pieces = {kind: functools.partial(operator, kind=kind, model=model,
                                      q_block=q_block)
              for kind in OPERATORS}
    pieces["expert_ffn"] = functools.partial(expert_layer, model=model)
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
    return [stage for kind, layer in zip(model["layers"], tree["layers"])
            for stage in ((kind, layer["op"]), ("expert_ffn",
                                                layer["ffn"]))]


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
    tests and ``chip_grads_solar.py``; :func:`train` never holds a
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
