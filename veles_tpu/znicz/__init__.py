"""znicz — the neural-network layer library.

The reference's znicz is an empty git submodule (reference:
.gitmodules:1-5, veles/znicz/ contains no files); its capability surface
is reconstructed from BASELINE.json configs (All2All, Conv, Pooling,
GradientDescent units, evaluators, decision, RBM pretraining) and the
core hooks that remain in the reference repo (kernels in ocl/ + cuda/,
veles/accelerated_units.py).  Everything here is a TracedUnit whose
forward composes into the workflow's single jitted step; backward comes
from jax.grad, and per-layer GradientDescent units apply their own
update rules inside the same jit.
"""

from .nn_units import (ForwardBase, GradientDescentBase,  # noqa: F401
                       gd_for)
from .all2all import (All2All, All2AllTanh, All2AllRelu,  # noqa: F401
                      All2AllStrictRelu, All2AllSigmoid,
                      All2AllSoftmax)
from .conv import (Conv, ConvTanh, ConvRelu, ConvStrictRelu,  # noqa: F401
                   ConvSigmoid, Deconv)
from .pooling import (Pooling, MaxPooling, MaxAbsPooling,  # noqa: F401
                      AvgPooling, StochasticPooling,
                      StochasticAbsPooling)
from .activation import (ActivationForward, ForwardTanh,  # noqa: F401
                         ForwardRelu, ForwardStrictRelu,
                         ForwardSigmoid, ForwardLog, ForwardTanhLog,
                         ForwardSinCos, ForwardMul)
from .dropout import DropoutForward  # noqa: F401
from .lrn import LRNormalizerForward  # noqa: F401
from .evaluator import EvaluatorSoftmax, EvaluatorMSE  # noqa: F401
from .gd import (GradientDescent, GDTanh, GDRelu,  # noqa: F401
                 GDStrictRelu, GDSigmoid, GDSoftmax, GDConv,
                 GDConvTanh, GDConvRelu, GDConvStrictRelu,
                 GDConvSigmoid, GDDeconv, GDMaxPooling,
                 GDMaxAbsPooling, GDAvgPooling, GDStochasticPooling,
                 GDStochasticAbsPooling, GDActivationTanh,
                 GDActivationRelu, GDActivationStrictRelu,
                 GDActivationSigmoid, GDActivationLog,
                 GDActivationTanhLog, GDActivationSinCos,
                 GDActivationMul, GDDropout, GDLRNormalizer)
from .rbm import (RBM, GDRBM, EvaluatorRBM, All2AllDeconv,  # noqa: F401
                  All2AllDeconvSigmoid, All2AllDeconvTanh)
from .attention import (Embedding, LMLayer,  # noqa: F401
                        TransformerBlock,
                        PipelinedTransformerStack, LMHead,
                        EvaluatorLM)
from .kohonen import (KohonenForward, KohonenTrainer,  # noqa: F401
                      GDKohonen)
from .decision import DecisionBase, DecisionGD  # noqa: F401
from .standard_workflow import StandardWorkflow  # noqa: F401
