"""veles.znicz_tpu — the neural-network plugin, TPU-native.

Rebuild of the reference znicz repo (SURVEY.md §2.4): every op is a
*pair* of units — a ``Forward`` and a matching ``GradientDescent*``
(explicit backprop as graph nodes). The upstream zoo's backwards are
derived by hand with a numpy oracle beside them; units added since
PR 28 (``ops/vjp_units.py``: RMS norm, gated short convolution,
grouped-query attention, SwiGLU, the no-drop expert layer) take their
backward from ``jax.vjp`` of the same traced forward, and their oracle
is the float32 reference under ``benchmark/reference/``.

Subpackages:

* ``ops``      — the unit zoo (all2all, conv, pooling, gd*, evaluator,
  normalization, dropout, activation, kohonen, rbm, attention, ...).
* ``models``   — sample workflows (MNIST, CIFAR10, AlexNet, Kohonen,
  RBM, Transformer LM), mirroring reference ``samples/``.
* ``parallel`` — mesh / sharding / collectives (ICI replacement for the
  reference's ZeroMQ master↔slave layer).
* ``utils``    — diagnostics, lr scheduling, rollback, image saving.
"""

from veles.znicz_tpu.nn_units import (  # noqa: F401
    Forward, GradientDescentBase, NNWorkflow,
    forward_unit, gradient_unit_for, gradient_for,
)
