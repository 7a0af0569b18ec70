"""Loss evaluator units.

Re-design of znicz ``evaluator.py`` [U] (SURVEY.md §2.4 "Evaluators"):

* :class:`EvaluatorSoftmax` — consumes softmax probabilities + integer
  labels; emits the fused softmax+CE gradient ``err_output =
  (p − onehot)/batch``, the minibatch wrong-count ``n_err``, the mean
  cross-entropy ``loss`` and (optionally) a confusion matrix.
* :class:`EvaluatorMSE` — consumes any output + a target array; emits
  ``err_output = 2(y−t)/batch`` and per-minibatch MSE metrics.

Padding contract (see ``veles/loader``): rows ≥ ``batch_size`` (the
true count) are masked out of both the gradient and the metrics, so
XLA static shapes and the numpy oracle agree exactly.
"""

import numpy

from veles.accelerated_units import AcceleratedUnit
from veles.memory import Array


class EvaluatorBase(AcceleratedUnit):
    """Common attrs: input (net output), err_output, batch_size."""

    scope_role = "loss"

    def __init__(self, workflow, **kwargs):
        super().__init__(workflow, **kwargs)
        self.input = None           # linked: last forward's output
        self.err_output = Array()   # gradient seed for the GD chain
        self.batch_size = None      # linked: loader.minibatch_size
        #: host metrics for Decision
        self.loss = 0.0
        self.n_err = 0
        #: worst sample of the last minibatch (reference max-error
        #: tracking [U]; consumed by ImageSaver): per-sample loss of
        #: the worst valid row + its minibatch-local position
        self.max_err = 0.0
        self.max_err_idx = 0

    def initialize(self, device=None, **kwargs):
        super().initialize(device=device, **kwargs)
        ishape = self.input.shape
        if not self.err_output or self.err_output.shape != ishape:
            self.err_output.reset(numpy.zeros(ishape, numpy.float32))

    def metric_sinks(self):
        """Where XLAStep publishes step outputs on the host unit."""
        return [("n_err", "n_err"), ("loss", "loss"),
                ("max_err", "max_err"), ("max_err_idx", "max_err_idx")]

    @staticmethod
    def _worst(xp, per_sample, fmask):
        """(max loss, argmax) over VALID rows; deterministic
        first-occurrence tie-break in both backends."""
        masked = per_sample * fmask
        return xp.max(masked), xp.argmax(masked)


class EvaluatorSoftmax(EvaluatorBase):
    """Fused softmax + cross-entropy loss."""

    def __init__(self, workflow, compute_confusion=False, **kwargs):
        super().__init__(workflow, **kwargs)
        self.labels = None          # linked: loader.minibatch_labels
        self.max_idx = None         # linked: softmax unit's argmax
        self.compute_confusion = compute_confusion
        self.confusion_matrix = Array()

    def initialize(self, device=None, **kwargs):
        super().initialize(device=device, **kwargs)
        n_classes = self.input.shape[-1]
        if self.compute_confusion and (
                not self.confusion_matrix
                or self.confusion_matrix.shape != (n_classes, n_classes)):
            self.confusion_matrix.reset(
                numpy.zeros((n_classes, n_classes), numpy.int32))

    def metric_sinks(self):
        sinks = super().metric_sinks()
        if self.compute_confusion:
            sinks.append(("confusion", "confusion_matrix"))
        return sinks

    # shared math ------------------------------------------------------

    def _compute(self, xp, probs, labels, max_idx, valid):
        b, n_classes = probs.shape
        mask = (xp.arange(b) < valid)
        fmask = mask.astype(probs.dtype)
        onehot = (labels[:, None] ==
                  xp.arange(n_classes)[None, :]).astype(probs.dtype)
        err = (probs - onehot) * fmask[:, None] / valid.astype(probs.dtype)
        p_true = xp.sum(probs * onehot, axis=-1)
        logp = xp.log(xp.maximum(p_true, 1e-30))
        loss = -xp.sum(logp * fmask) / valid.astype(probs.dtype)
        wrong = xp.sum((max_idx != labels) & mask)
        max_err, max_idx_b = self._worst(xp, -logp, fmask)
        conf = None
        if self.compute_confusion:
            pred_oh = (max_idx[:, None] ==
                       xp.arange(n_classes)[None, :]).astype(probs.dtype)
            conf = ((pred_oh * fmask[:, None]).T @ onehot) \
                .astype(xp.int32)
        return err, loss, wrong, max_err, max_idx_b, conf

    # oracle -----------------------------------------------------------

    def numpy_run(self):
        probs = self.input.map_read().mem
        labels = numpy.asarray(self.labels.map_read().mem, numpy.int32)
        max_idx = numpy.argmax(probs, axis=-1).astype(numpy.int32)
        valid = numpy.int32(int(self.batch_size))
        err, loss, wrong, max_err, max_err_idx, conf = self._compute(
            numpy, probs.astype(numpy.float32), labels, max_idx, valid)
        self.err_output.map_invalidate()
        self.err_output.mem[...] = err
        self.loss = float(loss)
        self.n_err = int(wrong)
        self.max_err = float(max_err)
        self.max_err_idx = int(max_err_idx)
        if conf is not None:
            self.confusion_matrix.map_write()
            self.confusion_matrix.mem += conf

    # traced -----------------------------------------------------------

    def xla_run(self, ctx):
        import jax.numpy as jnp
        # loss math in f32 regardless of the activation policy
        probs = ctx.get(self, "input").astype(jnp.float32)
        labels = ctx.get(self, "labels").astype(jnp.int32)
        max_idx = jnp.argmax(probs, axis=-1).astype(jnp.int32)
        valid = ctx.get(self, "batch_size")  # traced int scalar
        err, loss, wrong, max_err, max_err_idx, conf = self._compute(
            jnp, probs, labels, max_idx, valid)
        ctx.set(self, "err_output", err.astype(ctx.act_dtype))
        ctx.export("loss", loss)
        ctx.export("n_err", wrong.astype(jnp.int32))
        ctx.export("max_err", max_err)
        ctx.export("max_err_idx", max_err_idx.astype(jnp.int32))
        if conf is not None:
            ctx.export("confusion", conf)


class EvaluatorMSE(EvaluatorBase):
    """Mean-squared-error loss vs a target array."""

    def __init__(self, workflow, root_metric=True, **kwargs):
        super().__init__(workflow, **kwargs)
        self.target = None          # linked: loader.minibatch_targets
        self.root_metric = root_metric
        self.mse = 0.0

    def metric_sinks(self):
        return super().metric_sinks() + [("loss", "mse")]

    def _compute(self, xp, y, t, valid):
        b = y.shape[0]
        y2 = y.reshape(b, -1)
        t2 = t.reshape(b, -1)
        fmask = (xp.arange(b) < valid).astype(y2.dtype)
        diff = (y2 - t2) * fmask[:, None]
        err = 2.0 * diff / valid.astype(y2.dtype)
        per_sample = xp.mean(diff * diff, axis=1)
        mse = xp.sum(per_sample) / valid.astype(y2.dtype)
        max_err, max_idx = self._worst(xp, per_sample, fmask)
        return err, mse, max_err, max_idx

    def numpy_run(self):
        y = self.input.map_read().mem.astype(numpy.float32)
        t = self.target.map_read().mem.astype(numpy.float32)
        valid = numpy.float32(int(self.batch_size))
        err, mse, max_err, max_err_idx = self._compute(numpy, y, t, valid)
        self.err_output.map_invalidate()
        self.err_output.mem[...] = err.reshape(self.err_output.shape)
        self.mse = float(mse)
        self.loss = float(mse)
        self.n_err = 0
        self.max_err = float(max_err)
        self.max_err_idx = int(max_err_idx)

    def xla_run(self, ctx):
        import jax.numpy as jnp
        # loss math in f32 regardless of the activation policy
        y = ctx.get(self, "input").astype(jnp.float32)
        t = ctx.get(self, "target").astype(jnp.float32)
        valid = ctx.get(self, "batch_size").astype(jnp.float32)
        err, mse, max_err, max_err_idx = self._compute(jnp, y, t, valid)
        ctx.set(self, "err_output",
                err.reshape(y.shape).astype(ctx.act_dtype))
        ctx.export("loss", mse)
        ctx.export("n_err", jnp.int32(0))
        ctx.export("max_err", max_err)
        ctx.export("max_err_idx", max_err_idx.astype(jnp.int32))


class EvaluatorLM(EvaluatorBase):
    """Next-token softmax cross-entropy over (B, S, V) logits with
    integer labels (B, S); fused backward like EvaluatorSoftmax, but
    per TOKEN: err = (softmax − onehot)/(valid·S) on valid rows.
    ``n_err`` counts wrong token predictions (NEW — Transformer LM)."""

    def __init__(self, workflow, **kwargs):
        super().__init__(workflow, **kwargs)
        self.labels = None          # linked: loader.minibatch_labels

    @staticmethod
    def _softmax_ce_core(xp, logits, labels):
        """The ONE stable softmax-CE kernel (max-shift, logp, probs,
        onehot) shared by the full-batch ``_compute`` and the 1F1B
        fold's per-microbatch ``mb_loss_grad`` — their parity contract
        (summed microbatch grads == full-batch grads) rides on the
        numerics living in exactly one place."""
        vocab = logits.shape[-1]
        z = logits - logits.max(axis=-1, keepdims=True)
        logp = z - xp.log(xp.exp(z).sum(axis=-1, keepdims=True))
        probs = xp.exp(logp)
        onehot = (labels[..., None] ==
                  xp.arange(vocab)[None, None, :]).astype(logits.dtype)
        return logp, probs, onehot

    def _compute(self, xp, logits, labels, valid):
        b, s, vocab = logits.shape
        logp, probs, onehot = self._softmax_ce_core(xp, logits, labels)
        rowmask = (xp.arange(b) < valid).astype(logits.dtype)
        denom = valid.astype(logits.dtype) * float(s)
        err = (probs - onehot) * rowmask[:, None, None] / denom
        loss = -(logp * onehot).sum(axis=-1)
        loss = (loss * rowmask[:, None]).sum() / denom
        pred = xp.argmax(logits, axis=-1)
        wrong = ((pred != labels) & (rowmask[:, None] > 0)).sum()
        return err, loss, wrong

    @staticmethod
    def mb_loss_grad(xp, logits, labels, inv_denom):
        """Per-MICROBATCH fused softmax-CE gradient with the full-batch
        normalization baked in (``inv_denom`` = 1/(valid·S) of the
        whole minibatch): summing the returned (err, loss) over all
        microbatches reproduces :meth:`_compute` exactly. Rows whose
        labels carry the ``-1`` pad sentinel contribute nothing — the
        1F1B fold (ops/transformer_stack.py) marks invalid rows that
        way because the row/valid comparison needs global row indices
        a microbatch slice no longer has."""
        logp, probs, onehot = EvaluatorLM._softmax_ce_core(
            xp, logits, labels)
        mask = (labels >= 0).astype(logits.dtype)
        err = (probs - onehot) * mask[..., None] * inv_denom
        loss = -((logp * onehot).sum(axis=-1) * mask).sum() * inv_denom
        return err, loss

    def numpy_run(self):
        logits = self.input.map_read().mem.astype(numpy.float32)
        labels = numpy.asarray(self.labels.map_read().mem,
                               numpy.int64)
        valid = numpy.int32(int(self.batch_size))
        err, loss, wrong = self._compute(numpy, logits, labels, valid)
        self.err_output.map_invalidate()
        self.err_output.mem[...] = err
        self.loss = float(loss)
        self.n_err = int(wrong)

    def xla_run(self, ctx):
        import jax.numpy as jnp
        # loss math in f32 regardless of the activation policy
        logits = ctx.get(self, "input").astype(jnp.float32)
        labels = ctx.get(self, "labels").astype(jnp.int32)
        valid = ctx.get(self, "batch_size")
        err, loss, wrong = self._compute(jnp, logits, labels, valid)
        ctx.set(self, "err_output", err.astype(ctx.act_dtype))
        ctx.export("loss", loss)
        ctx.export("n_err", wrong.astype(jnp.int32))


class EvaluatorHeadsLM(EvaluatorLM):
    """Cross entropy of ``heads`` prediction heads over one state: the
    logits (B, S, heads x V) are head ``j``'s (B, S, V) in columns
    ``[j V, (j + 1) V)``, and head ``j`` predicts the token ``j + 1``
    ahead, ``labels[:, t + j]`` (the labels are the next-token shift).
    The positions whose target lies past the row have none and are
    masked; the loss is the mean over heads of each head's mean over
    its targets,

        loss = 1/heads sum_j sum_{t < S - j} CE_j(t) / (valid (S - j)),

    and ``err = (softmax - onehot) mask_j / (heads valid (S - j))``
    its gradient; ``n_err`` counts head 0's wrong next tokens."""

    def __init__(self, workflow, heads=1, **kwargs):
        super().__init__(workflow, **kwargs)
        self.heads = int(heads)

    def _compute(self, xp, logits, labels, valid):
        b, s, wide = logits.shape
        n = self.heads
        logits = logits.reshape(b, s, n, wide // n)
        targets = xp.stack([xp.concatenate(
            [labels[:, j:], xp.full((b, j), -1, labels.dtype)], axis=1)
            for j in range(n)], axis=2)                     # (b, s, n)
        logp, probs, onehot = self._softmax_ce_core(xp, logits, targets)
        rows = (xp.arange(b) < valid).astype(logits.dtype)
        weight = (targets >= 0).astype(logits.dtype) \
            * rows[:, None, None] / (
                valid.astype(logits.dtype) * n
                * (s - xp.arange(n)).astype(logits.dtype))
        err = ((probs - onehot) * weight[..., None]).reshape(b, s, wide)
        loss = -((logp * onehot).sum(axis=-1) * weight).sum()
        wrong = ((xp.argmax(logits[:, :, 0], axis=-1) != labels)
                 & (rows[:, None] > 0)).sum()
        return err, loss, wrong


class EvaluatorLoopLM(EvaluatorLM):
    """The loss of a looped layer stack (``znicz_tpu.loop.Loop``):
    every pass ``t`` of ``steps`` exits through the one head, and a
    learned gate ``lambda_t = sigmoid(gate_t)`` says, per token, how
    much of the prediction leaves there:

        p_t  = lambda_t * prod_{j<t} (1 - lambda_j)   (t < steps)
        p_T  = prod_{j<T} (1 - lambda_j)              (sums to 1)
        loss = mean over tokens of [sum_t p_t CE_t - beta * H(p)]

    with ``CE_t`` the token's cross entropy at exit ``t`` and ``H`` the
    entropy of ``(p_1 .. p_T)``. The loop drives it in three calls:
    ``loop_begin`` makes ``p`` from the passes' gates, ``xla_run`` is
    ONE exit's visit (the logits of one exit live at a time: its
    ``err_output`` is ``p_t (softmax - onehot) / tokens``), and
    ``loop_end`` has every exit's per-token ``CE_t``, so it closes the
    loss and gives the gates' cotangent — through the weights ``p_t``
    AND the entropy term."""

    def __init__(self, workflow, steps=2, entropy_weight=0.0, **kwargs):
        super().__init__(workflow, **kwargs)
        self.steps = int(steps)
        self.entropy_weight = float(entropy_weight)
        self.gate = None            # linked: the gate unit's tap

    @staticmethod
    def exit_log_mass(gate):
        """log p, (T, ...) from the gates (T, ...), in log space: no
        product of sigmoids underflows to a 0 whose log is taken."""
        import jax
        import jax.numpy as jnp
        stay = jax.nn.log_sigmoid(-gate)            # log(1 - lambda)
        before = jnp.cumsum(stay, axis=0) - stay    # sum over j < t
        leave = jnp.concatenate(
            [jax.nn.log_sigmoid(gate[:-1]), jnp.zeros_like(gate[:1])])
        return leave + before

    def _token_mask(self, ctx, shape):
        import jax.numpy as jnp
        valid = ctx.get(self, "batch_size")
        rows = (jnp.arange(shape[0]) < valid).astype(jnp.float32)
        return rows[:, None] / (valid.astype(jnp.float32) * shape[1])

    def loop_begin(self, ctx):
        """-> what each exit's visit is given, stacked by pass."""
        import jax.numpy as jnp
        gate = ctx.get(self, "gate").astype(jnp.float32)
        return {"exit_mass": jnp.exp(self.exit_log_mass(gate))}

    def xla_run(self, ctx):
        import jax.numpy as jnp
        logits = ctx.get(self, "input").astype(jnp.float32)
        labels = ctx.get(self, "labels").astype(jnp.int32)
        logp, probs, onehot = self._softmax_ce_core(jnp, logits, labels)
        mask = self._token_mask(ctx, labels.shape)
        if ctx.train:
            weight = ctx.get(self, "exit_mass") * mask
            ctx.set(self, "err_output",
                    ((probs - onehot) * weight[..., None])
                    .astype(ctx.act_dtype))
        ctx.export("exit_ce", -(logp * onehot).sum(axis=-1))
        wrong = (jnp.argmax(logits, axis=-1) != labels) & (mask > 0)
        ctx.export("exit_wrong", wrong.sum().astype(jnp.int32))

    def loop_end(self, ctx, exits):
        """``exits``: the visits' exports stacked by pass. Exports the
        step's metrics; in a training step -> the taps' cotangents,
        ``{"gate": (T, B, S)}``."""
        import jax
        import jax.numpy as jnp
        gate = ctx.get(self, "gate").astype(jnp.float32)
        ce = exits["exit_ce"]
        mask = self._token_mask(ctx, ce.shape[1:])

        def total(gate):
            logp = self.exit_log_mass(gate)
            p = jnp.exp(logp)
            per_token = (p * ce).sum(0) \
                + self.entropy_weight * (p * logp).sum(0)
            return (per_token * mask).sum(), p

        (loss, p), dgate = jax.value_and_grad(total, has_aux=True)(gate)
        ctx.export("loss", loss)
        ctx.export("n_err", exits["exit_wrong"][-1])
        if not ctx.train:
            return {}
        mass = (p * mask).sum((1, 2))
        for t in range(self.steps):
            ctx.export("loop_ce_%d" % (t + 1), (ce[t] * mask).sum())
            ctx.export("loop_mass_%d" % (t + 1), mass[t])
        ctx.export("loop_expected",
                   (mass * jnp.arange(1, self.steps + 1)).sum())
        return {"gate": dgate}

    # -- counters: the last training step's exits, by pass ---------------

    def metric_sinks(self):
        return super().metric_sinks() + [
            ("loop_%s_%d" % (what, t), "step_%s_%d" % (what, t))
            for t in range(1, self.steps + 1) for what in ("ce", "mass")
        ] + [("loop_expected", "step_expected")]

    def metrics_published(self, fresh):
        """``XLAStep``'s hook, once a step's sinks are filled: a
        training step's (only that exports ``loop_*``) moves the
        counters and sets the gauges."""
        if "step_expected" not in fresh:
            return
        from veles import telemetry
        telemetry.counter(
            "veles_loop_steps_total", "Training steps a looped layer "
            "stack ran").inc()
        telemetry.counter(
            "veles_loop_passes_total", "Passes of a looped layer stack "
            "over its one set of weights, training steps"
        ).inc(self.steps)
        telemetry.gauge(
            "veles_loop_expected_exit_pass", "Last step: the pass at "
            "which a token's prediction leaves, in expectation"
        ).set(self.step_expected)
        loss = telemetry.gauge(
            "veles_loop_exit_loss", "Last step: mean cross entropy of a "
            "looped stack's exit, by pass", ("pass",))
        mass = telemetry.gauge(
            "veles_loop_exit_mass", "Last step: mean share of a token's "
            "prediction that leaves at a pass (the exit distribution)",
            ("pass",))
        for t in range(1, self.steps + 1):
            loss.labels(str(t)).set(getattr(self, "step_ce_%d" % t))
            mass.labels(str(t)).set(getattr(self, "step_mass_%d" % t))
