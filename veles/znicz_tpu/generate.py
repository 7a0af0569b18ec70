"""Autoregressive LM generation with a KV cache (NEW — completes the
LM story: train → generate → export; no reference counterpart).

The training side runs whole sequences through the unit graph; this
module walks the SAME trained forward units and builds a jitted
incremental decoder from their parameters:

* **prefill** — one full causal forward over the prompt (the units'
  own ``xla_run`` formulas), keeping each attention layer's K/V;
* **decode** — ``lax.scan`` over output positions: a single token's
  activations flow through per-token formulas (embedding row + fixed
  sinusoidal position, LN/FFN/MoE/TokenDense are sequence-free), and
  each attention layer attends its one query against the growing K/V
  cache (``dynamic_update_slice`` into a preallocated (B,H,max,dh)
  buffer, position-masked softmax) — O(S) per token instead of O(S²)
  re-running the full forward.

Greedy when ``temperature == 0``, else softmax sampling via
``jax.random.categorical``. Exactness contract (verified in
tests/test_generate.py): for DENSE models, greedy KV-cached decode
equals the naive re-run-the-whole-forward argmax decode. MoE models
generate fine but are NOT bit-identical to the full re-run: Switch
capacity ranks tokens within whatever batch the router sees — B
tokens per decode step here vs B·S in a full forward — so borderline
capacity drops can differ (the standard trade-off of incremental MoE
decoding).

Parameters are passed INTO the jitted functions (not baked as
constants), and the compiled prefill/decode pair is cached on the
workflow per output signature — repeated generate() calls with the
same shapes are compile-free and always use the current weights.

The per-layer decode formulas (:func:`attn_decode`,
:func:`block_decode`) take the position as a PER-SEQUENCE vector —
the batch-joinable carry the serving decode plane
(``veles/serving/decode.py``) needs: continuous batching packs
sequences of different lengths into one decode step, each row
writing its K/V at its own position and masking its own horizon. The
offline path here simply passes a constant vector (every row at the
same position).

Supported unit types: Embedding, MultiHeadAttention (causal),
LayerNorm, TransformerFFN, MoEFFN, TokenDense(+RELU),
TransformerBlockStack, Dropout (identity at inference). Anything else
raises — mirroring the C++ export contract.
"""

import weakref

import numpy

from veles.znicz_tpu.ops.embedding import (
    EmbeddingForward, sinusoidal_positions)


def _unit_params(workflow, unit):
    """The unit's parameter tree: device-resident values when the
    compiled step owns them, else the host Arrays."""
    step = getattr(workflow, "xla_step", None)
    if step is not None and step.params is not None:
        tree = step.params.get(unit.name)
        if tree:
            return dict(tree)
    out = {}
    for name in getattr(unit, "PARAMS", ()):
        arr = getattr(unit, name, None)
        if arr is not None and arr:
            out[name] = numpy.asarray(arr.map_read().mem)
    return out


def attn_decode(x, pos, kv, p, heads, include_bias, residual,
                dot=None):
    """One decode step through an attention layer: x (B,1,D), kv =
    (K, V) buffers (B,H,max,dh). ``pos`` is a PER-SEQUENCE int32
    vector (B,) — each row writes its K/V at its own position and
    attends its own horizon (a scalar is broadcast). Returns
    (y, new_kv). This is the batch-joinable carry the continuous
    batcher rides: rows admitted at different times decode in one
    step."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    dot = dot or jnp.matmul
    b, _, d = x.shape
    dh = d // heads
    K, V = kv
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
    qkv = dot(x, p["weights"])
    if include_bias:
        qkv = qkv + p["bias"]
    split = (lambda t: t.reshape(b, 1, heads, dh)
             .transpose(0, 2, 1, 3))
    q = split(qkv[..., :d])
    k1 = split(qkv[..., d:2 * d])
    v1 = split(qkv[..., 2 * d:])
    # per-row scatter at (b, :, pos[b], :) — vmap'd so every row of
    # a joined batch lands at its own write position
    upd = jax.vmap(
        lambda buf, new, pb: lax.dynamic_update_slice(
            buf, new, (0, pb, 0)))
    K = upd(K, k1, pos)
    V = upd(V, v1, pos)
    scale = numpy.float32(1.0 / numpy.sqrt(dh))
    scores = dot(q, K.transpose(0, 1, 3, 2))[:, :, 0, :] * scale
    mask = jnp.arange(K.shape[2])[None, :] > pos[:, None]  # (B,max)
    scores = jnp.where(mask[:, None, :], jnp.float32(-1e9), scores)
    probs = jnp.exp(scores - scores.max(-1, keepdims=True))
    probs = probs / probs.sum(-1, keepdims=True)
    ctx = dot(probs[:, :, None, :], V)             # (B,H,1,dh)
    merged = ctx.transpose(0, 2, 1, 3).reshape(b, 1, d)
    y = dot(merged, p["weights_out"])
    if include_bias:
        y = y + p["bias_out"]
    if residual:
        y = y + x
    return y, (K, V)


def block_decode(x, pos, kv, lp, heads, eps, dot=None):
    """One decode step through a stacked transformer block (the
    attention uses the cache; LN/FFN are the shared formulas).
    ``pos`` is the per-sequence position vector of
    :func:`attn_decode`."""
    import jax.numpy as jnp
    from veles.znicz_tpu.ops import activations as A
    from veles.znicz_tpu.ops.layernorm import ln_fwd
    from veles.znicz_tpu.parallel.pipeline import ACT

    dot = dot or jnp.matmul
    a, kv = attn_decode(
        x, pos, kv,
        {"weights": lp["weights"], "bias": lp["bias"],
         "weights_out": lp["weights_out"],
         "bias_out": lp["bias_out"]},
        heads, True, True, dot)
    n1 = ln_fwd(jnp, a, lp["ln1_g"], lp["ln1_b"], eps)
    h = A.ACTIVATIONS[ACT][0](jnp, dot(n1, lp["ffn_w1"])
                              + lp["ffn_b1"])
    fo = dot(h, lp["ffn_w2"]) + lp["ffn_b2"] + n1
    y = ln_fwd(jnp, fo, lp["ln2_g"], lp["ln2_b"], eps)
    return y, kv


def _plan(workflow):
    """(steps, n_caches): an ordered decode plan over the forward
    units. Each step is (kind, unit, cache_slot); attention-bearing
    steps get cache slot indices. Parameters are NOT captured here —
    they are gathered fresh per generate() call and passed into the
    jitted functions."""
    from veles.znicz_tpu.ops.attention import (
        MultiHeadAttention, TokenDenseBase, TransformerFFN)
    from veles.znicz_tpu.ops.dropout import DropoutForward
    from veles.znicz_tpu.ops.layernorm import LayerNormForward
    from veles.znicz_tpu.ops.moe import MoEFFN
    from veles.znicz_tpu.ops.transformer_stack import (
        TransformerBlockStack)

    steps = []
    n_caches = 0
    for unit in workflow.forwards:
        if isinstance(unit, EmbeddingForward):
            steps.append(("embed", unit, None))
        elif isinstance(unit, MultiHeadAttention):
            if not unit.causal:
                raise ValueError(
                    "%s: generation needs causal attention"
                    % unit.name)
            steps.append(("attn", unit, n_caches))
            n_caches += 1
        elif isinstance(unit, TransformerBlockStack):
            if not unit.causal:
                raise ValueError(
                    "%s: generation needs causal attention"
                    % unit.name)
            steps.append(("stack", unit, n_caches))
            n_caches += unit.layers
        elif isinstance(unit, (LayerNormForward, TransformerFFN,
                               MoEFFN, TokenDenseBase)):
            steps.append(("token", unit, None))
        elif isinstance(unit, DropoutForward):
            continue   # identity at inference
        else:
            raise ValueError(
                "cannot generate through unit %s (%s)"
                % (unit.name, type(unit).__name__))
    if not steps or steps[0][0] != "embed":
        raise ValueError("generation needs an embedding first")
    return steps, n_caches


def _token_apply(unit, p, x):
    """Run a sequence-free unit's shared formula on (B,1,D)."""
    import jax.numpy as jnp
    from veles.znicz_tpu.ops.attention import (
        TokenDenseBase, TransformerFFN)
    from veles.znicz_tpu.ops.layernorm import LayerNormForward
    from veles.znicz_tpu.ops.moe import MoEFFN

    if isinstance(unit, LayerNormForward):
        return unit._forward(jnp, x, p["weights"], p["bias"])
    if isinstance(unit, TransformerFFN):
        y, _ = unit._forward(jnp, x, p["weights"], p["bias"],
                             p["weights2"], p["bias2"], jnp.matmul)
        return y
    if isinstance(unit, MoEFFN):
        y, _ = unit._forward(jnp, x, p)
        return y
    if isinstance(unit, TokenDenseBase):
        return unit._forward(jnp, x, p["weights"], p.get("bias"),
                             jnp.matmul)
    raise AssertionError(type(unit))


def _build_fns(workflow, steps, n_caches, maxlen, temperature,
               n_tokens, top_k, top_p):
    """(prefill_fn, decode_fn) pure in their parameters: every jitted
    tensor (param trees, prompt ids, carry) is an argument."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from veles.znicz_tpu.parallel.pipeline import block_fwd

    emb_unit = steps[0][1]
    positions = jnp.asarray(
        sinusoidal_positions(maxlen, emb_unit.dim)) \
        if emb_unit.add_positions else None

    def embed_full(table, ids):
        y = table[ids]
        if positions is not None:
            y = y + positions[:ids.shape[1]]
        return y

    def prefill(ptrees, ids):
        """Dense causal forward; (logits_last, kv at maxlen)."""
        x = embed_full(ptrees[0]["weights"], ids)
        caches = [None] * n_caches
        for (kind, unit, slot), p in zip(steps[1:], ptrees[1:]):
            if kind == "attn":
                y, (q, k, v, probs, merged) = unit._fwd_core(
                    jnp, x, p["weights"], p.get("bias"),
                    p["weights_out"], p.get("bias_out"))
                caches[slot] = (k, v)
                x = y
            elif kind == "stack":
                for l in range(unit.layers):
                    lp = {k2: p[k2][l] for k2 in unit.PARAMS}
                    x, cache = block_fwd(jnp, x, lp, unit.heads,
                                         unit.causal, unit.eps)
                    caches[slot + l] = (cache["k"], cache["v"])
            else:
                x = _token_apply(unit, p, x)
        pad = maxlen - ids.shape[1]
        kv = tuple(
            (jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0))),
             jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0))))
            for k, v in caches)
        return x[:, -1, :], kv

    def sample(logits, k):
        if temperature <= 0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        logits = logits / jnp.float32(temperature)
        if top_k or top_p:
            # ONE shared descending sort serves both filters
            srt = jnp.sort(logits, axis=-1)[:, ::-1]
            if top_k:
                kth = srt[:, min(int(top_k), srt.shape[1]) - 1]
                logits = jnp.where(logits < kth[:, None],
                                   jnp.float32(-1e9), logits)
            if top_p:
                # nucleus: keep the smallest prefix of the sorted
                # probs whose mass exceeds top_p (the top token
                # always stays: its cumsum-minus-self is 0 < top_p)
                probs = jax.nn.softmax(srt, axis=-1)
                keep = jnp.cumsum(probs, axis=-1) - probs \
                    < jnp.float32(top_p)
                cutoff = jnp.min(
                    jnp.where(keep, srt, jnp.float32(numpy.inf)),
                    axis=-1, keepdims=True)
                logits = jnp.where(logits < cutoff,
                                   jnp.float32(-1e9), logits)
        return jax.random.categorical(k, logits, axis=-1) \
            .astype(jnp.int32)

    def decode_step(ptrees, carry, _):
        token, pos, kv, key = carry
        key, sub = jax.random.split(key)
        x = ptrees[0]["weights"][token][:, None, :]
        if positions is not None:
            # pos is a per-sequence vector (constant here, varying in
            # the serving continuous batch): gather each row's own
            # position embedding
            x = x + positions[pos][:, None, :]
        kv = list(kv)
        for (kind, unit, slot), p in zip(steps[1:], ptrees[1:]):
            if kind == "attn":
                x, kv[slot] = attn_decode(
                    x, pos, kv[slot], p, unit.heads,
                    unit.include_bias, unit.residual, jnp.matmul)
            elif kind == "stack":
                for l in range(unit.layers):
                    lp = {k2: p[k2][l] for k2 in unit.PARAMS}
                    x, kv[slot + l] = block_decode(
                        x, pos, kv[slot + l], lp, unit.heads,
                        unit.eps, jnp.matmul)
            else:
                x = _token_apply(unit, p, x)
        nxt = sample(x[:, 0, :], sub)
        return (nxt, pos + 1, tuple(kv), key), nxt

    def run(ptrees, ids, key):
        logits, kv = prefill(ptrees, ids)
        key, sub = jax.random.split(key)
        first = sample(logits, sub)
        carry = (first,
                 jnp.full((ids.shape[0],), ids.shape[1], jnp.int32),
                 kv, key)
        if n_tokens > 1:
            _, rest = lax.scan(
                lambda c, u: decode_step(ptrees, c, u), carry, None,
                length=n_tokens - 1)
            return jnp.concatenate([first[:, None], rest.T], axis=1)
        return first[:, None]

    return jax.jit(run)


def _cache_key(sig, steps):
    """Compiled-decoder cache key: the shape/sampling signature plus
    a WEAKREF per step unit. ``id(u)`` keyed here once — but a freed
    unit's reallocated id can alias a stale compiled decoder built
    for different weights/architecture (any cache keyed by the id of
    a collectable object has this hazard). Weakrefs compare by
    referent identity while alive and never equal a new object after
    death, and their hash is cached at insert time, so dead keys stay
    safely hashable until evicted."""
    return sig + (tuple(weakref.ref(u) for _, u, _ in steps),)


def _evict_dead(cache):
    """Drop cache entries holding a dead unit ref (the unit was
    garbage-collected; its compiled decoder can never be hit again —
    and must not linger while a reallocated id could have aliased
    it)."""
    for key in [k for k in cache
                if any(r() is None for r in k[-1])]:
        del cache[key]


def generate(workflow, prompt_ids, n_tokens, temperature=0.0,
             key=None, top_k=None, top_p=None):
    """Generate ``n_tokens`` continuations for ``prompt_ids`` (B, P)
    from a trained LM workflow. Returns int32 (B, n_tokens).
    ``temperature=0`` is greedy; otherwise softmax sampling, optionally
    truncated to the ``top_k`` highest logits and/or the ``top_p``
    nucleus (smallest prefix of probability mass)."""
    import jax
    import jax.numpy as jnp

    prompt_ids = numpy.asarray(prompt_ids, numpy.int32)
    if prompt_ids.ndim != 2 or prompt_ids.shape[1] < 1:
        raise ValueError("prompt_ids must be (B, P>=1)")
    n_tokens = int(n_tokens)
    if n_tokens <= 0:
        return numpy.zeros(prompt_ids.shape[:1] + (0,), numpy.int32)
    # normalize disabled truncation values so behavior-identical
    # calls share one compiled decoder
    top_k = int(top_k) if top_k else None
    top_p = float(top_p) if top_p is not None and top_p < 1.0 \
        else None
    if top_k is not None and top_k < 1:
        raise ValueError("top_k must be >= 1, got %r" % (top_k,))
    if top_p is not None and top_p <= 0:
        raise ValueError("top_p must be in (0, 1], got %r" % (top_p,))
    b, p_len = prompt_ids.shape
    maxlen = p_len + n_tokens
    steps, n_caches = _plan(workflow)
    if key is None:
        key = jax.random.PRNGKey(0)
    # bounded FIFO of compiled decoders: each distinct
    # (batch, prompt_len, n_tokens, temperature, top_k, top_p)
    # signature costs one XLA compile; callers with many prompt
    # lengths should pad to a few bucket sizes themselves
    cache = workflow.__dict__.setdefault("_generate_jit_cache", {})
    _evict_dead(cache)
    sig = _cache_key(
        (b, p_len, n_tokens, float(temperature), top_k, top_p),
        steps)
    if sig not in cache:
        if len(cache) >= 16:
            cache.pop(next(iter(cache)))
        cache[sig] = _build_fns(workflow, steps, n_caches, maxlen,
                                float(temperature), n_tokens,
                                top_k, top_p)
    ptrees = [_unit_params(workflow, unit) for _, unit, _ in steps]
    out = cache[sig](ptrees, jnp.asarray(prompt_ids), key)
    return numpy.asarray(out, numpy.int32)
