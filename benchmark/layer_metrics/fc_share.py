"""Share of the device's busy time in the fully connected layers, in
percent: the units of the ``All2All*`` classes and the gradient units
of ``ops/gd.py`` that go with them, their weight update
(``veles.update``) left out. It is the part of ``conv_share`` that is
no convolution (on a TPU the FC products are of HLO category
convolution too), with the layers' elementwise work."""

import re

from benchmark.reduce import scopes

FC = re.compile(r"All2All\w*|GradientDescent"
                r"|GD(Tanh|RELU|StrictRELU|Sigmoid|Softmax)")


def read(ctx):
    return scopes.share_percent(
        ctx, lambda op: op.sub != "update" and op.cls is not None
        and FC.fullmatch(op.cls) is not None)
