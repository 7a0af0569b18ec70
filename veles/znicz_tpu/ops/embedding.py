"""Token embedding unit pair (NEW — Transformer LM path).

Lookup table (vocab, dim) with optional fixed sinusoidal positional
encoding added; backward scatter-adds the error into the table rows.
"""

import numpy

from veles.znicz_tpu.nn_units import (
    Forward, GradientDescentBase, forward_unit, gradient_for)


def sinusoidal_positions(seq_len, dim):
    pos = numpy.arange(seq_len, dtype=numpy.float32)[:, None]
    i = numpy.arange(dim, dtype=numpy.float32)[None, :]
    angle = pos / numpy.power(10000.0, (2.0 * (i // 2)) / dim)
    enc = numpy.where(i.astype(numpy.int64) % 2 == 0,
                      numpy.sin(angle), numpy.cos(angle))
    return enc.astype(numpy.float32)


@forward_unit("embedding")
class EmbeddingForward(Forward):
    """ids (B,S) int → (B,S,D) float, + sinusoidal positions."""

    PARAMS = ("weights",)

    def __init__(self, workflow, vocab_size=None, dim=None,
                 add_positions=True, **kwargs):
        super().__init__(workflow, **kwargs)
        if not (vocab_size and dim):
            raise ValueError("embedding needs vocab_size and dim")
        self.vocab_size = int(vocab_size)
        self.dim = int(dim)
        self.add_positions = add_positions
        self.include_bias = False

    def output_shape_for(self, ishape):
        return tuple(ishape) + (self.dim,)

    def initialize(self, device=None, **kwargs):
        super().initialize(device=device, **kwargs)
        self.init_weights((self.vocab_size, self.dim),
                          self.vocab_size, self.dim)
        oshape = self.output_shape_for(self.input.shape)
        if not self.output or self.output.shape != oshape:
            self.output.reset(numpy.zeros(oshape, numpy.float32))
        self._positions = sinusoidal_positions(
            self.input.shape[1], self.dim) if self.add_positions \
            else None

    def _forward(self, xp, ids, table):
        y = table[ids]
        if self._positions is not None:
            y = y + xp.asarray(self._positions)
        return y

    def numpy_run(self):
        ids = self.input.map_read().mem.astype(numpy.int64)
        self.output.map_invalidate()
        self.output.mem[...] = self._forward(
            numpy, ids, self.weights.map_read().mem)

    def xla_run(self, ctx):
        import jax.numpy as jnp
        ids = ctx.get(self, "input").astype(jnp.int32)
        table = ctx.unit_params(self)["weights"]
        ctx.set(self, "output",
                self._forward(jnp, ids, table).astype(
                    self.stream_dtype(ctx)))


@gradient_for(EmbeddingForward)
class GDEmbedding(GradientDescentBase):
    """Scatter-add error rows into the table; no err_input (ids are
    not differentiable)."""

    def __init__(self, workflow, **kwargs):
        kwargs.setdefault("need_err_input", False)
        super().__init__(workflow, **kwargs)

    def numpy_run(self):
        f = self.forward
        ids = f.input.map_read().mem.astype(numpy.int64).ravel()
        err = numpy.asarray(self.err_output.map_read().mem,
                            numpy.float32).reshape(-1, f.dim)
        grad = numpy.zeros((f.vocab_size, f.dim), numpy.float32)
        numpy.add.at(grad, ids, err)
        self.update_weights_numpy(grad, None)

    def xla_run(self, ctx):
        import jax.numpy as jnp
        f = self.forward
        ids = ctx.get(f, "input").astype(jnp.int32).ravel()
        err = ctx.get(self, "err_output").reshape(-1, f.dim)
        grad = jnp.zeros((f.vocab_size, f.dim),
                         jnp.float32).at[ids].add(err)
        self.update_weights_xla(ctx, grad, None)
