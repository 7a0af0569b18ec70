"""Convolution backward units.

Re-design of znicz ``gd_conv.py`` [U] (SURVEY.md §2.4 "Conv backward"):
``err_input`` via col2im scatter, ``ΔW`` as GEMM over unpacked patches
— the oracle keeps that exact structure. The traced path expresses both
as convolutions so XLA keeps everything on the MXU:

* ``err_input`` = transposed conv of dz with the forward weights
  (input-dilated ``conv_general_dilated`` — the classic adjoint);
* ``grad_W``    = conv of input with dz as the filter (batch as the
  contraction dim via dimension-number transposes).
"""

import numpy

from veles.znicz_tpu.nn_units import GradientDescentBase, gradient_for
from veles.znicz_tpu.ops import activations as A
from veles.znicz_tpu.ops import conv_math as CM
from veles.znicz_tpu.ops.conv import (
    Conv, ConvTanh, ConvRELU, ConvStrictRELU, ConvSigmoid)


class GDConvBase(GradientDescentBase):
    ACTIVATION = "linear"

    def _deriv(self, xp, err, y):
        d = A.ACTIVATIONS[self.ACTIVATION][1](xp, y)
        return err if isinstance(d, float) else err * d

    # -- oracle ---------------------------------------------------------

    def numpy_run(self):
        f = self.forward
        x = f.input.map_read().mem.astype(numpy.float32)
        y = f.output.map_read().mem
        err = numpy.asarray(self.err_output.map_read().mem,
                            numpy.float32).reshape(y.shape)
        dz = self._deriv(numpy, err, y)
        w = f.weights.map_read().mem           # (K, ky*kx*C)
        b_, oy, ox, k = dz.shape
        dz2 = dz.reshape(-1, k)
        cols = CM.im2col(numpy, x, f.ky, f.kx, f.sliding, f.padding)
        grad_w = dz2.T @ cols.reshape(-1, cols.shape[-1])
        grad_b = dz2.sum(axis=0) if self.include_bias else None
        if self.need_err_input:
            dcols = dz2 @ w                    # (B*oy*ox, ky*kx*C)
            ei = CM.col2im(numpy, dcols.reshape(cols.shape), x.shape,
                           f.ky, f.kx, f.sliding, f.padding)
            self.err_input.map_invalidate()
            self.err_input.mem[...] = ei
        self.update_weights_numpy(grad_w, grad_b)

    # -- traced ---------------------------------------------------------

    def xla_run(self, ctx):
        import jax
        import jax.numpy as jnp
        f = self.forward
        x = ctx.get(f, "input")
        y = ctx.get(f, "output")
        err = ctx.get(self, "err_output").reshape(y.shape)
        dz = self._deriv(jnp, err, y)
        w = ctx.unit_params(f)["weights"]
        c = x.shape[-1]
        cd = ctx._compiler.device.compute_dtype
        top, bottom, left, right = self.padding_
        sy, sx = f.sliding
        w_hwio = w.reshape(f.n_kernels, f.ky, f.kx, c) \
            .transpose(1, 2, 3, 0)
        # stride remainders: input rows/cols the forward conv never read
        ry = (x.shape[1] + top + bottom - f.ky) % sy
        rx = (x.shape[2] + left + right - f.kx) % sx

        if self.need_err_input:
            # adjoint conv: dilate dz by the stride, swap in/out
            # channels, flip the kernel spatially
            w_flip = w_hwio[::-1, ::-1, :, :].transpose(0, 1, 3, 2)
            ei = jax.lax.conv_general_dilated(
                dz.astype(cd), w_flip.astype(cd),
                window_strides=(1, 1),
                padding=((f.ky - 1 - top, f.ky - 1 - bottom + ry),
                         (f.kx - 1 - left, f.kx - 1 - right + rx)),
                lhs_dilation=(sy, sx),
                dimension_numbers=("NHWC", "HWIO", "NHWC"),
                preferred_element_type=jnp.float32)
            ctx.set(self, "err_input", ei.astype(ctx.act_dtype))

        # grad_w[k, ky*kx*C]: conv with batch as the contraction dim;
        # the forward stride becomes rhs_dilation. This form holds for
        # ANY stride, and needs no im2col+GEMM materialization of the
        # strided case.
        s2d = CM.s2d_block(f.ky, f.kx, f.sliding, c)
        if s2d:
            # space-to-depth transform (conv_math.py): the weight-grad
            # conv contracts over batch+space with the packed s*s*C
            # channels feeding the MXU lanes (18 -> 12.4 ms for
            # AlexNet conv1 on a v5e; the forward measured SLOWER
            # under the same transform and keeps the plain conv)
            xs = CM.s2d_pack_input(jnp, x, s2d, self.padding_)
            gw = jax.lax.conv_general_dilated(
                xs.transpose(3, 1, 2, 0).astype(cd),  # C',H',W',B
                dz.transpose(1, 2, 0, 3).astype(cd),  # oy,ox,B,K
                window_strides=(1, 1), padding=((0, 0), (0, 0)),
                dimension_numbers=("NHWC", "HWIO", "NHWC"),
                preferred_element_type=jnp.float32)  # (C',kyb',kxb',K)
            grad_w = CM.s2d_unpack_wgrad(
                jnp, gw, f.n_kernels, f.ky, f.kx, c, s2d)
        else:
            gw = jax.lax.conv_general_dilated(
                x.transpose(3, 1, 2, 0).astype(cd),   # C,H,W,B "NHWC"
                dz.transpose(1, 2, 0, 3).astype(cd),  # oy,ox,B,K "HWIO"
                window_strides=(1, 1),
                padding=((top, bottom - ry), (left, right - rx)),
                rhs_dilation=(sy, sx),
                dimension_numbers=("NHWC", "HWIO", "NHWC"),
                preferred_element_type=jnp.float32)   # -> (C,ky,kx,K)
            grad_w = gw.transpose(3, 1, 2, 0) \
                .reshape(f.n_kernels, f.ky * f.kx * c)
        # bias grad: default = an MXU matvec (ones @ dz2) with f32
        # accumulate, which XLA fuses with the activation-derivative
        # mask into a convert_reduce loop fusion. The fused_bias_grad
        # hatch (on TPU with $VELES_FUSED_BIAS_GRAD=1) takes it OUT of
        # XLA: the hand-fused Pallas kernel (ops/pallas_grads.py)
        # recomputes mask+convert internally and block-reduces in f32,
        # leaving no bias reduce for XLA's fusion pass to duplicate
        # the producer into. Neither form's cost on the chip has been
        # measured under the current benchmark
        # (docs/repro_convert_reduce.py times the variants).
        if self.include_bias:
            grad_b = self.bias_grad_xla(
                ctx, err.reshape(-1, f.n_kernels),
                y.reshape(-1, f.n_kernels))
            if grad_b is None:
                dz2 = dz.reshape(-1, f.n_kernels)
                ones = jnp.ones((1, dz2.shape[0]), dz2.dtype)
                grad_b = jax.lax.dot_general(
                    ones, dz2, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)[0]
        else:
            grad_b = None
        self.update_weights_xla(ctx, grad_w, grad_b)

    @property
    def padding_(self):
        return self.forward.padding


@gradient_for(Conv)
class GradientDescentConv(GDConvBase):
    ACTIVATION = "linear"


@gradient_for(ConvTanh)
class GDTanhConv(GDConvBase):
    ACTIVATION = "tanh"


@gradient_for(ConvRELU)
class GDRELUConv(GDConvBase):
    ACTIVATION = "relu"


@gradient_for(ConvStrictRELU)
class GDStrictRELUConv(GDConvBase):
    ACTIVATION = "strict_relu"


@gradient_for(ConvSigmoid)
class GDSigmoidConv(GDConvBase):
    ACTIVATION = "sigmoid"
