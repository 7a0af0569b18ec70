"""Transformer-base LM sample (BASELINE config #5 — NEW).

Decoder-only LM: Embedding(+positions) → N × [MHA(residual) →
LayerNorm → FFN(residual) → LayerNorm] → TokenDense(vocab logits),
trained next-token on a deterministic synthetic periodic-sequence
corpus (the pattern-copy task needs real attention to solve, and
converges quickly at small scale).

Config under ``root.lm``; sequence parallelism / ring attention for
long contexts lives in ``veles.znicz_tpu.parallel.ring`` and is
exercised by the parallel tests.
"""

import numpy

from veles import prng
from veles.config import root
from veles.loader.fullbatch import FullBatchLoader
from veles.znicz_tpu.ops.evaluator import (
    EvaluatorHeadsLM, EvaluatorLM, EvaluatorLoopLM)
from veles.znicz_tpu.standard_workflow import StandardWorkflow

root.lm.update({
    # text_file: path to a utf-8 corpus → character-level LM via
    # TextLMLoader (vocab inferred); None → the synthetic periodic
    # pattern task
    "loader": {"minibatch_size": 64, "n_train": 2048, "n_valid": 256,
               "seq_len": 32, "vocab": 16, "max_period": 6,
               "text_file": None, "valid_ratio": 0.1},
    # attn_block: single-chip flash-style blocked attention (exact;
    # O(S*block) score memory instead of O(S^2)); None = dense
    # moe_experts > 0 swaps the dense FFN for a top-1-routed MoE FFN
    # (ops/moe.py) with that many experts per layer; shard them over
    # chips with root.lm.parallel.expert. stacked=True fuses the block
    # stack into ONE transformer_stack unit (lax.scan over layers —
    # flat compile time in depth, and the vehicle for pipeline
    # parallelism via root.lm.parallel.pipe).
    # attn_impl (with attn_block set): None = chosen from S and the
    # device (MultiHeadAttention.PALLAS_AUTO_MIN_S: the Pallas TPU
    # kernels of parallel/pallas_attention.py from S=256 up on a TPU,
    # else the lax.scan flash formulation); "scan" / "pallas" force
    # either. pallas_tile: explicit kernel tile override (None =
    # measured auto, up to 512 — the VMEM escape hatch for large
    # head dims)
    # remat (with stacked=True): activation-checkpoint the block scan
    # — stash only layer inputs, recompute caches in the backward;
    # ~+1/3 compute for an O(heads*seq/12) stash cut (the (B, S)
    # envelope knob for the stacked path; docs/PARALLELISM.md); with
    # block="pre_norm" the SwiGLU feed-forwards stash only their input
    # and run their forward again in the backward
    # block: "post_ln" (the block above) or "pre_norm": pre-norm (RMS)
    # residual layers, each an operator and a feed-forward, no bias, no
    # absolute positions, a final RMS norm before the head. `layers`
    # is a count of "full_attention" layers or, in either block, the
    # list of the layers' operators; pre_norm also has "conv" (gated
    # short convolution, conv_kernel taps), and its "full_attention" is
    # heads query heads over kv_heads K/V heads of head_dim, q/k RMS
    # norm, rotary positions at rope_theta. Its feed-forward is SwiGLU
    # of width ffn_hidden in the first dense_layers layers, then a
    # no-drop top-moe_top_k layer of moe_experts experts of width
    # moe_hidden (sigmoid scores times moe_scaling, selection biases
    # drawn at moe_bias_stddev), of which this job holds experts_held =
    # [lo, hi) (None: all; the rest live on other chips and their part
    # of the sum is left out), beside a shared expert of width
    # moe_shared_hidden that every token passes (0: none). pre_norm's
    # "plain_attention" is "full_attention" without the q/k norm, its
    # "gated_nope_attention" that without rotary positions and with an
    # elementwise sigmoid output gate, and "delta_attention" a gated
    # delta-rule linear-attention layer (ops/delta_attention.py) of
    # delta_heads heads of delta_head_dim, delta_conv_kernel taps and
    # gates of rank delta_gate_rank. operators: {operator's name: the
    # GQAttention keywords that are ITS OWN, over the model's} for a
    # model whose attention layers differ in more than a switch - a
    # layer pattern whose head count, rotary base, rotated share of a
    # head (rotary_dim), rope_scaling (a rope_parameters entry of
    # rope_type "yarn"), window (a query sees itself and the window - 1
    # tokens before it) or gate ("elementwise" | "head": one sigmoid
    # gate a head and token) change by layer type; a name the table
    # below has keeps its switches under the model's keywords, a new
    # name ("sliding_attention") is the model's to define, and the
    # kernel and its tile follow from the shape and the window, never
    # from a key. norm: "pre" (one gain
    # before each sub-layer) or "sandwich" (a second gain on the
    # sub-layer's output, before the residual add). ut_steps > 1 runs
    # the layers and the final norm ut_steps times in a row over the
    # same weights (znicz_tpu.loop.Loop: the passes are a loop of
    # the compiled step, a layer is recomputed in the backward from its
    # saved input, the solver runs once a step on the gradients' sum);
    # every pass's state exits through the one head, an exit gate
    # (ops/exit_gate.py) weighs the exits per token, and the loss is
    # the expected cross entropy less exit_entropy_weight times the
    # exit distribution's entropy (EvaluatorLoopLM). pre_norm's
    # "eva_attention" is EVA attention (ops/eva_attention.py): exact
    # softmax over the query's own window of eva_window tokens and over
    # one learned summary of each eva_chunk tokens of every earlier
    # window, in one softmax. residual_dtype ("float32"; None: the
    # device's activation type) is the type of the residual stream
    # between pre_norm's units, logits_dtype that of the head's
    # logits; pred_heads > 1 makes the head that many untied heads in
    # one product, head j predicting the token j + 1 ahead, the loss
    # their mean (EvaluatorHeadsLM). Every key is a shape or a constant
    # the model's config states, none a tuning knob.
    "model": {"dim": 64, "heads": 4, "layers": 2, "ffn_hidden": 128,
              "attn_block": None, "attn_impl": None,
              "pallas_tile": None, "moe_experts": 0,
              "moe_capacity_factor": 2.0, "moe_aux_weight": 0.01,
              "stacked": False, "remat": False,
              "block": "post_ln", "kv_heads": None, "head_dim": None,
              "dense_layers": 0, "moe_hidden": None, "moe_top_k": 1,
              "experts_held": None, "moe_scaling": 1.0,
              "moe_bias_stddev": 0.0, "conv_kernel": 3,
              "rope_theta": 1e6, "norm_eps": 1e-5, "norm": "pre",
              "ut_steps": 1, "exit_entropy_weight": 0.0,
              "moe_shared_hidden": 0, "delta_heads": None,
              "delta_head_dim": None, "delta_conv_kernel": 4,
              "delta_gate_rank": None, "operators": {},
              "eva_window": 2048, "eva_chunk": 16,
              "residual_dtype": None, "logits_dtype": None,
              "pred_heads": 1},
    "train": {"learning_rate": 0.05, "gradient_moment": 0.9,
              "weights_decay": 0.0},
    "decision": {"max_epochs": 8, "fail_iterations": 50},
    # sharding axes (SURVEY.md §5.7/§5.8): seq > 1 routes attention
    # through the ppermute ring (sequence parallelism); model > 1
    # shards the transformer matmuls Megatron-style via GSPMD; data
    # > 1 shards the batch. All from config alone — e.g.
    #   velescli ... root.lm.parallel.seq=8
    # ep_routing: "gather" (GSPMD-partitioned dense dispatch; O(E)
    # token bandwidth, fine on small meshes) or "alltoall" (explicit
    # shard_map lax.all_to_all exchange, O(tokens) — the at-scale EP;
    # parallel/expert.py)
    # schedule: pipeline schedule with pipe > 1 — "gpipe" (stash all
    # microbatches) or "1f1b" (PipeDream-flush, min(M, P-s) stash +
    # forward recompute; parallel/pipeline.py)
    "parallel": {"seq": 1, "model": 1, "data": 1, "expert": 1,
                 "pipe": 1, "microbatches": 4, "ep_routing": "gather",
                 "schedule": "gpipe"},
})


def text_vocab(path, text=None):
    """Sorted character vocabulary of a text file (or of ``text``
    when the caller already read it) → (itos, stoi)."""
    if text is None:
        with open(path, "r", encoding="utf-8",
                  errors="replace") as f:
            text = f.read()
    chars = sorted(set(text))
    if not chars:
        raise ValueError("%s: empty corpus" % path)
    return chars, {c: i for i, c in enumerate(chars)}


def _tail_valid_order(n, n_valid):
    """[valid | train] index order with validation as the TAIL of
    the corpus (shared by both LM loaders)."""
    return numpy.concatenate([
        numpy.arange(n - n_valid, n), numpy.arange(0, n - n_valid)])


class TextLMLoader(FullBatchLoader):
    """Character-level corpus loader: a text file becomes (B, S)
    next-char windows (NEW — the real-data path for the LM sample;
    configure with ``root.lm.loader.text_file``). The synthetic
    periodic loader below remains the no-data default."""

    def load_data(self):
        cfg = root.lm.loader
        path = cfg.text_file
        s = cfg.get("seq_len", 32)
        with open(path, "r", encoding="utf-8", errors="replace") as f:
            text = f.read()
        # _loader_factory stashes the vocab it already computed for
        # this exact file; the file can change on disk between factory
        # time (which sized cfg.vocab / the embedding) and now, so the
        # cache is only trusted when it still covers the text we just
        # read — a mismatch means the model was built for a different
        # corpus, which is unrecoverable here
        # NB: underscore names live as plain object attributes on the
        # Config node (config.py:84), not in the _items tree that
        # .get() consults — getattr is the only working read path
        cached = getattr(cfg, "_vocab_cache", None)
        if cached and cached[0] == path:
            vocab = set(cached[1])
            extra = sorted(set(text) - vocab)
            if extra:
                raise ValueError(
                    "%s changed on disk after the model was sized: "
                    "%d characters (%r...) are not in the %d-char "
                    "vocabulary the embedding was built for; restart "
                    "the run" % (path, len(extra),
                                 "".join(extra[:8]), len(vocab)))
            self.itos = list(cached[1])
            self.stoi = {c: i for i, c in enumerate(self.itos)}
        else:
            self.itos, self.stoi = text_vocab(path, text)
        stream = numpy.fromiter(
            (self.stoi[c] for c in text), numpy.int32, len(text))
        n = (len(stream) - 1) // s
        if n < 2:
            raise ValueError(
                "%s: corpus too short for seq_len %d" % (path, s))
        data = numpy.stack([stream[i * s:i * s + s + 1]
                            for i in range(n)])
        # held-out tail as validation, at least one sequence
        n_valid = max(1, int(n * cfg.get("valid_ratio", 0.1)))
        data = data[_tail_valid_order(n, n_valid)]
        self.original_data.mem = data[:, :-1]
        self.original_labels.mem = data[:, 1:]
        self.class_lengths = [0, n_valid, n - n_valid]
        self.serve_dtype = numpy.int32

    def encode(self, text):
        bad = sorted(set(text) - set(self.stoi))
        if bad:
            raise ValueError(
                "prompt characters %r are not in the corpus "
                "vocabulary (%d known characters)"
                % ("".join(bad), len(self.itos)))
        return numpy.array([[self.stoi[c] for c in text]],
                           numpy.int32)

    def decode(self, ids):
        return "".join(self.itos[int(i)] for i in numpy.ravel(ids))


class PeriodicLMLoader(FullBatchLoader):
    """Sequences repeating a random pattern of random period ≤
    max_period; labels are the next-token shift. Prediction beyond one
    period requires attending back — a true attention task."""

    def load_data(self):
        cfg = root.lm.loader
        gen = prng.get("lm_data")
        n = cfg.get("n_train", 2048) + cfg.get("n_valid", 256)
        s = cfg.get("seq_len", 32)
        vocab = cfg.get("vocab", 16)
        max_p = cfg.get("max_period", 6)
        seqs = numpy.zeros((n, s + 1), numpy.int32)
        for i in range(n):
            p = int(gen.randint(2, max_p + 1))
            pattern = gen.randint(0, vocab, p)
            reps = (s + 1 + p - 1) // p
            seqs[i] = numpy.tile(pattern, reps)[:s + 1]
        self.original_data.mem = seqs[:, :-1]
        self.original_labels.mem = seqs[:, 1:]
        n_valid = cfg.get("n_valid", 256)
        self.class_lengths = [0, n_valid, n - n_valid]
        # serve token ids as ints, not floats
        self.serve_dtype = numpy.int32
        # [valid | train] layout expected by the loader
        order = _tail_valid_order(n, n_valid)
        self.original_data.mem = self.original_data.mem[order]
        self.original_labels.mem = self.original_labels.mem[order]


def layer_operators(m):
    """``root.lm.model.layers`` as the list of the layers' operators:
    an int N is N ``"full_attention"`` layers."""
    layers = m.layers
    if isinstance(layers, int):
        return ["full_attention"] * layers
    if not isinstance(layers, (list, tuple)) or not layers:
        raise ValueError("layers is a count or the list of the "
                         "layers' operators, got %r" % (layers,))
    return list(layers)


#: pre_norm's attention operators: what each changes of ``GQAttention``
ATTENTION_OPERATORS = {
    "full_attention": {},
    "plain_attention": {"qk_norm": False},
    "gated_nope_attention": {"qk_norm": False, "rope": False,
                             "gate": True}}


def attention_operators(m):
    """``ATTENTION_OPERATORS`` under the model's own
    (``root.lm.model.operators``): {name: what it changes of
    ``GQAttention`` and of the model's heads, rope_theta, ...}."""
    own = m.get("operators")
    own = own.to_dict() if hasattr(own, "to_dict") else dict(own or {})
    return dict(ATTENTION_OPERATORS, **{
        name: dict(ATTENTION_OPERATORS.get(name, {}), **keywords)
        for name, keywords in own.items()})


def loop_passes(m):
    """``ut_steps``: how often the layers run in a row (1: once)."""
    steps = m.get("ut_steps", 1)
    if steps != 1 and m.get("block", "post_ln") != "pre_norm":
        raise ValueError("ut_steps=%r needs block='pre_norm'" % (steps,))
    return steps


def pre_norm_body(m, t):
    """The layers of ``block="pre_norm"`` between embedding and head
    (the keys' meaning is beside ``root.lm.model``). With ``ut_steps``
    > 1 each layer dict says its place in the loop (``"loop"``: a
    layer's operator starts a recomputed segment)."""
    operators = layer_operators(m)
    attention = attention_operators(m)
    # every operator a pre_norm layer can have
    known = ("conv", "delta_attention", "eva_attention") \
        + tuple(attention)
    if set(operators) - set(known):
        raise ValueError("block='pre_norm' has the operators %s, got %r"
                         % (", ".join(map(repr, known)), operators))
    norm = m.get("norm", "pre")
    if norm not in ("pre", "sandwich"):
        raise ValueError("norm is 'pre' or 'sandwich', got %r" % (norm,))
    steps = loop_passes(m)
    looped = steps > 1
    busy = {k: v for k, v in root.lm.parallel.to_dict().items()
            if k in ("seq", "model", "expert", "pipe") + (
                ("data",) if looped else ()) and v > 1}
    if busy or m.get("stacked"):
        raise ValueError(
            "block='pre_norm' trains on the per-unit path, one chip or "
            "(at ut_steps=1) data-parallel; not with stacked=%r / "
            "parallel %r" % (m.get("stacked"), busy))
    dense = m.get("dense_layers", 0) >= len(operators)
    own_counters = {"delta_attention", "eva_attention"} & set(operators)
    if looped and (not dense or own_counters or heads_of(m) > 1):
        raise ValueError(
            "ut_steps=%r loops layers with a SwiGLU feed-forward "
            "(dense_layers = the layers' number), no delta_attention or "
            "eva_attention and one prediction head: the expert, "
            "delta-rule and EVA layers' counters are one visit's"
            % (steps,))
    if norm == "sandwich" and not (
            dense and set(operators) <= set(attention)):
        raise ValueError("norm='sandwich' is for attention layers over "
                         "a SwiGLU feed-forward")
    eps = m.get("norm_eps", 1e-5)
    sandwich = {"sandwich": True} if norm == "sandwich" else {}
    stream = stream_keys(m)
    remat = {"recompute": True} if m.get("remat") else {}
    attending = dict(attention_kernel_keys(m), heads=m.heads,
                     kv_heads=m.get("kv_heads"),
                     head_dim=m.get("head_dim"),
                     rope_theta=m.get("rope_theta", 1e6), eps=eps,
                     **sandwich)
    body = []
    for index, kind in enumerate(operators):
        if kind == "conv":
            body.append({
                "type": "short_conv",
                "->": dict(kernel=m.get("conv_kernel", 3), eps=eps,
                           **stream),
                "<-": dict(t), "loop": "segment"})
        elif kind == "delta_attention":
            body.append({
                "type": "delta_attention",
                "->": {"heads": m.get("delta_heads") or m.heads,
                       "head_dim": m.get("delta_head_dim")
                       or m.get("head_dim"),
                       "kernel": m.get("delta_conv_kernel", 4),
                       "gate_rank": m.get("delta_gate_rank"),
                       "eps": eps, **stream},
                "<-": dict(t), "loop": "segment"})
        elif kind == "eva_attention":
            body.append({
                "type": "eva_attention",
                "->": dict(attending, chunk=m.get("eva_chunk", 16),
                           local_window=m.get("eva_window", 2048),
                           **stream),
                "<-": dict(t), "loop": "segment"})
        else:
            body.append({
                "type": "gqa_attention",
                "->": dict(attending, **attention[kind], **stream),
                "<-": dict(t), "loop": "segment"})
        if index < m.get("dense_layers", 0):
            body.append({"type": "swiglu_ffn",
                         "->": dict(hidden=m.ffn_hidden, eps=eps,
                                    **sandwich, **stream, **remat),
                         "<-": dict(t), "loop": "body"})
        else:
            body.append({
                "type": "expert_ffn",
                "->": {"experts": m.moe_experts,
                       "top_k": m.get("moe_top_k", 1),
                       "hidden": m.moe_hidden,
                       "experts_held": m.get("experts_held"),
                       "scaling": m.get("moe_scaling", 1.0),
                       "bias_stddev": m.get("moe_bias_stddev", 0.0),
                       "shared_hidden": m.get("moe_shared_hidden", 0),
                       "eps": eps, **stream},
                "<-": dict(t)})
    body.append({"type": "rms_norm", "->": dict(eps=eps, **stream),
                 "<-": dict(t), "loop": "body"})
    if looped:
        body.append({"type": "exit_gate", "<-": dict(t), "loop": "body"})
    return body


def stream_keys(m):
    """The units' ``output_dtype`` where ``residual_dtype`` sets one:
    the embedding's and pre_norm's layers' outputs and err_inputs."""
    dtype = m.get("residual_dtype")
    if dtype and m.get("block", "post_ln") != "pre_norm":
        raise ValueError("residual_dtype is pre_norm's, got block %r"
                         % m.get("block", "post_ln"))
    return {"output_dtype": dtype} if dtype else {}


def heads_of(m):
    """``pred_heads``: the head's prediction heads (1: next token)."""
    heads = m.get("pred_heads")
    heads = 1 if heads is None else int(heads)
    if heads < 1:
        raise ValueError("pred_heads is at least 1, got %r" % (heads,))
    return heads


def attention_kernel_keys(m):
    """The keys that choose the attention proper's kernel, the same
    for every block's attention unit."""
    return {"attn_block_size": m.get("attn_block"),
            "attn_impl": m.get("attn_impl"),
            "pallas_tile": m.get("pallas_tile")}


def build_layers():
    m = root.lm.model
    t = root.lm.train.to_dict()
    block = m.get("block", "post_ln")
    if block not in ("post_ln", "pre_norm"):
        raise ValueError("unknown block %r" % (block,))
    pre_norm = block == "pre_norm"
    vocab = root.lm.loader.vocab
    # pre-norm: rotary positions inside the attention units, no bias
    layers = [{"type": "embedding",
               "->": dict({"vocab_size": vocab, "dim": m.dim},
                          **({"add_positions": False} if pre_norm
                             else {}), **stream_keys(m)),
               "<-": dict(t)}]
    logits = m.get("logits_dtype")
    head = {"type": "token_dense",
            "->": dict({"output_features": vocab * heads_of(m)},
                       **({"include_bias": False} if pre_norm else {}),
                       **({"output_dtype": logits} if logits else {})),
            "<-": dict(t), "loop": "exit"}
    if pre_norm:
        return layers + pre_norm_body(m, t) + [head]
    if m.get("stacked"):
        if m.get("moe_experts"):
            raise ValueError(
                "stacked=True builds dense-FFN blocks; it cannot "
                "honour moe_experts=%r (use the per-layer model for "
                "MoE)" % m.moe_experts)
        if m.get("attn_block") or m.get("attn_impl"):
            raise ValueError(
                "stacked=True uses dense attention inside the block "
                "scan; attn_block=%r / attn_impl=%r are not supported "
                "there (use the per-layer model for flash/pallas "
                "attention)"
                % (m.get("attn_block"), m.get("attn_impl")))
        layers += [
            {"type": "transformer_stack",
             "->": {"layers": m.layers, "heads": m.heads,
                    "hidden": m.ffn_hidden, "causal": True,
                    "remat": bool(m.get("remat"))},
             "<-": dict(t)},
            head]
        return layers
    if m.get("moe_experts"):
        ffn_layer = {
            "type": "moe_ffn",
            "->": {"experts": m.moe_experts, "hidden": m.ffn_hidden,
                   "residual": True,
                   "capacity_factor": m.get("moe_capacity_factor",
                                            2.0)},
            "<-": dict(t, aux_weight=m.get("moe_aux_weight", 0.01))}
    else:
        ffn_layer = {"type": "transformer_ffn",
                     "->": {"hidden": m.ffn_hidden, "residual": True},
                     "<-": dict(t)}
    operators = layer_operators(m)
    if set(operators) - {"full_attention"}:
        raise ValueError("block='post_ln' has the operator "
                         "'full_attention' alone, got %r" % (operators,))
    for _ in operators:
        layers += [
            {"type": "attention",
             "->": dict(attention_kernel_keys(m), heads=m.heads,
                        causal=True, residual=True),
             "<-": dict(t)},
            {"type": "layernorm", "<-": dict(t)},
            dict(ffn_layer),
            {"type": "layernorm", "<-": dict(t)},
        ]
    layers.append(head)
    return layers


def looped_stack(wf):
    """The workflow's ``znicz_tpu.loop.Loop`` (None at ut_steps 1),
    from the places ``pre_norm_body`` gave the layers."""
    steps = loop_passes(root.lm.model)
    if steps <= 1:
        return None
    from veles.znicz_tpu.loop import Loop
    segments, exits = [], []
    for spec, unit in zip(wf.layers_config, wf.forwards):
        place = spec.get("loop")
        if place == "segment":
            segments.append([unit])
        elif place == "body":
            segments[-1].append(unit)
        elif place == "exit":
            exits.append(unit)
    return Loop(steps, segments, exits)


def lm_evaluator_factory(wf, last):
    m = root.lm.model
    steps = loop_passes(m)
    if steps > 1:
        ev = EvaluatorLoopLM(
            wf, name="evaluator", steps=steps,
            entropy_weight=m.get("exit_entropy_weight", 0.0))
        ev.link_attrs(wf.forwards[-2], "gate")   # the exit gate's tap
    elif heads_of(m) > 1:
        ev = EvaluatorHeadsLM(wf, name="evaluator", heads=heads_of(m))
    else:
        ev = EvaluatorLM(wf, name="evaluator")
    ev.link_attrs(last, ("input", "output"))
    ev.link_attrs(wf.loader, ("labels", "minibatch_labels"),
                  ("batch_size", "minibatch_size"))
    return ev


class TransformerLMWorkflow(StandardWorkflow):
    """StandardWorkflow + config-driven sharding: after initialize,
    ``root.lm.parallel`` picks ring attention (seq), Megatron TP
    (model) and/or batch DP (data) — no code required in user
    configs."""

    def create_workflow(self):
        super().create_workflow()
        #: units that run several times a step (``ut_steps`` > 1)
        self.loop = looped_stack(self)
        return self

    def initialize(self, device=None, **kwargs):
        out = super().initialize(device=device, **kwargs)
        self._setup_parallel()
        return out

    def _setup_parallel(self):
        if self.xla_step is None:       # numpy oracle backend
            return
        cfg = root.lm.get("parallel")
        spec = cfg.to_dict() if hasattr(cfg, "to_dict") else \
            dict(cfg or {})
        seq = int(spec.get("seq", 1))
        model = int(spec.get("model", 1))
        data = int(spec.get("data", 1))
        expert = int(spec.get("expert", 1))
        pipe = int(spec.get("pipe", 1))
        if max(seq, model, data, expert, pipe) <= 1:
            return
        from veles.znicz_tpu import parallel
        # ONE composed mesh over every requested axis: all shardings
        # must agree on device assignment or jit rejects the step
        axes = {}
        if data > 1:
            axes["data"] = data
        if seq > 1:
            axes["seq"] = seq
        if model > 1:
            axes["model"] = model
        if expert > 1:
            axes["expert"] = expert
        if pipe > 1:
            axes["pipe"] = pipe
        # meshed from the devices this workflow's Device holds, not
        # from whatever jax's default platform is
        mesh = parallel.make_mesh(axes, self.device.jax_devices)
        if seq > 1:
            parallel.setup_sequence_parallel(
                self, mesh, batch_axis="data" if data > 1 else None)
        if data > 1:
            parallel.setup_data_parallel(self, mesh, refresh=False)
        if model > 1:
            # skips attention units already owned by the ring path
            parallel.setup_tensor_parallel(self, mesh, refresh=False)
        if expert > 1:
            parallel.setup_expert_parallel(
                self, mesh, refresh=False,
                routing=str(spec.get("ep_routing", "gather")))
        if pipe > 1:
            parallel.setup_pipeline_parallel(
                self, mesh,
                microbatches=int(spec.get("microbatches", 4)),
                batch_axis="data" if data > 1 else None,
                refresh=False,
                schedule=str(spec.get("schedule", "gpipe")))
        self.xla_step.refresh_device()


def _loader_factory():
    """Pick the corpus: a text file (char-level, vocab inferred and
    written back into the config BEFORE layers are built) or the
    synthetic periodic task."""
    cfg = root.lm.loader
    if cfg.get("text_file"):
        itos, _ = text_vocab(cfg.text_file)
        cfg.vocab = len(itos)
        cfg._vocab_cache = (cfg.text_file, "".join(itos))
        cls = TextLMLoader
    else:
        cls = PeriodicLMLoader
    return lambda wf: cls(wf, name="loader",
                          minibatch_size=cfg.minibatch_size)


def create_workflow(name="TransformerLM", **kwargs):
    cfg = root.lm
    factory = _loader_factory()
    return TransformerLMWorkflow(
        None, name=name,
        layers=build_layers(),
        loader_factory=factory,
        evaluator_factory=lm_evaluator_factory,
        decision_config=cfg.decision.to_dict(),
        **kwargs)


def run(load, main):
    factory = _loader_factory()
    load(TransformerLMWorkflow,
         layers=build_layers(),
         loader_factory=factory,
         evaluator_factory=lm_evaluator_factory,
         decision_config=root.lm.decision.to_dict())
    main()
