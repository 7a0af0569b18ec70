"""The znicz unit zoo (SURVEY.md §2.4).

Importing this package registers every forward/gradient unit pair in
the MatchingObject registry, so ``StandardWorkflow`` layer types
resolve. Modules mirror the reference file layout (``all2all.py``,
``gd.py``, ``conv.py``, ...) with TPU-native internals.
"""

from veles.znicz_tpu.ops.all2all import (  # noqa: F401
    All2All, All2AllTanh, All2AllRELU, All2AllStrictRELU,
    All2AllSigmoid, All2AllSoftmax,
)
from veles.znicz_tpu.ops.gd import (  # noqa: F401
    GradientDescent, GDTanh, GDRELU, GDStrictRELU, GDSigmoid, GDSoftmax,
)
from veles.znicz_tpu.ops.evaluator import (  # noqa: F401
    EvaluatorBase, EvaluatorSoftmax, EvaluatorMSE, EvaluatorLM,
    EvaluatorLoopLM,
)
from veles.znicz_tpu.ops.conv import (  # noqa: F401
    Conv, ConvTanh, ConvRELU, ConvStrictRELU, ConvSigmoid,
)
from veles.znicz_tpu.ops.gd_conv import (  # noqa: F401
    GradientDescentConv, GDTanhConv, GDRELUConv, GDStrictRELUConv,
    GDSigmoidConv,
)
from veles.znicz_tpu.ops.pooling import (  # noqa: F401
    MaxPooling, MaxAbsPooling, AvgPooling, StochasticPooling,
)
from veles.znicz_tpu.ops.gd_pooling import (  # noqa: F401
    GDMaxPooling, GDMaxAbsPooling, GDAvgPooling, GDStochasticPooling,
)
from veles.znicz_tpu.ops.normalization import (  # noqa: F401
    LRNormalizerForward, LRNormalizerBackward,
)
from veles.znicz_tpu.ops.dropout import (  # noqa: F401
    DropoutForward, DropoutBackward,
)
from veles.znicz_tpu.ops import activation  # noqa: F401
from veles.znicz_tpu.ops.cutter import Cutter, GDCutter, ZeroFiller  # noqa: F401
from veles.znicz_tpu.ops.deconv import (  # noqa: F401
    Deconv, GDDeconv, Depooling, GDDepooling,
)
from veles.znicz_tpu.ops.mean_disp_normalizer import (  # noqa: F401
    MeanDispNormalizer,
)
from veles.znicz_tpu.ops.layernorm import (  # noqa: F401
    LayerNormForward, GDLayerNorm,
)
from veles.znicz_tpu.ops.embedding import (  # noqa: F401
    EmbeddingForward, GDEmbedding,
)
from veles.znicz_tpu.ops.attention import (  # noqa: F401
    TokenDense, TokenDenseRELU, GDTokenDense, GDTokenDenseRELU,
    TransformerFFN, GDTransformerFFN,
    MultiHeadAttention, GDMultiHeadAttention,
)
from veles.znicz_tpu.ops.moe import (  # noqa: F401
    MoEFFN, GDMoEFFN,
)
from veles.znicz_tpu.ops.rmsnorm import RMSNorm, GDRMSNorm  # noqa: F401
from veles.znicz_tpu.ops.short_conv import (  # noqa: F401
    ShortConv, GDShortConv,
)
from veles.znicz_tpu.ops.swiglu import SwiGLUFFN, GDSwiGLUFFN  # noqa: F401
from veles.znicz_tpu.ops.gqa_attention import (  # noqa: F401
    GQAttention, GDGQAttention,
)
from veles.znicz_tpu.ops.delta_attention import (  # noqa: F401
    DeltaAttention, GDDeltaAttention,
)
from veles.znicz_tpu.ops.exit_gate import (  # noqa: F401
    ExitGate, GDExitGate,
)
from veles.znicz_tpu.ops.expert_ffn import (  # noqa: F401
    ExpertFFN, GDExpertFFN,
)
from veles.znicz_tpu.ops.transformer_stack import (  # noqa: F401
    TransformerBlockStack, GDTransformerBlockStack,
)
from veles.znicz_tpu.ops.kohonen import (  # noqa: F401
    KohonenForward, KohonenTrainer,
)
from veles.znicz_tpu.ops.rbm import (  # noqa: F401
    Binarization, TiedAll2AllSigmoid, BatchWeights, GradientRBM,
    EvaluatorRBM,
)
