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
