"""Neural-network unit library — the Znicz-equivalent layer set.

The reference's NN plugin (veles/znicz submodule, absent from the checkout;
surface reconstructed in SURVEY.md §2.8) provided forward units paired with
gradient-descent backward units, evaluators, decision logic and a
StandardWorkflow graph builder. This package re-implements that capability
TPU-first: every forward unit declares a *pure* ``apply(params, x)``
function; backward passes come from ``jax.grad`` of the composed
forward+loss instead of hand-written per-layer backward kernels, and the
whole forward/backward/update for a minibatch fuses into one jitted SPMD
step (see train_step.py).
"""

from .nn_units import ForwardBase, GradientDescentBase, MATCHING  # noqa
from .all2all import (All2All, All2AllTanh, All2AllRelu,
                      All2AllSigmoid, All2AllSoftmax)  # noqa
from .activation import (ForwardTanh, ForwardRelu, ForwardStrictRelu,
                         ForwardSigmoid, ForwardLog, ForwardMul)  # noqa
from .conv import Conv, ConvTanh, ConvRelu, ConvSigmoid  # noqa
from .pooling import MaxPooling, AvgPooling, StochasticPooling  # noqa
from .deconv import Deconv  # noqa
from .depooling import Depooling  # noqa
from .dropout import DropoutForward  # noqa
from .normalization import LRNormalizerForward  # noqa
from .evaluator import EvaluatorSoftmax, EvaluatorMSE  # noqa
from .decision import DecisionGD, DecisionMSE  # noqa
from .lr_adjust import (LearningRateAdjust, step_exp, inv,  # noqa
                        exp_decay, warmup_cosine)
from .rnn import LSTM, RNN, GDLSTM, GDRNN  # noqa
from .ssm import SSMBlock, GDSSMBlock  # noqa
from .kohonen import KohonenForward, KohonenTrainer  # noqa
from .rbm import RBM, RBMTrainer  # noqa
from .cutter import Cutter  # noqa
from .channel_split import ChannelSplitter, ChannelMerger  # noqa
from .zerofill import ZeroFiller  # noqa
from .image_saver import ImageSaver  # noqa
from .nn_plotting import Weights2D, KohonenHits  # noqa
from .attention import MultiHeadAttention, attention_core  # noqa
from .moe import MoEFFN  # noqa
from . import sampling  # noqa
from . import speculative  # noqa
from . import beam  # noqa
from .transformer import (TransformerBlock, MeanPool,  # noqa
                          PositionalEmbedding, Embedding, LMHead)
from .hybrid import HybridBlock, GDHybridBlock, FinalNorm, GDFinalNorm  # noqa
from .evaluator import EvaluatorSoftmaxSeq  # noqa
from .variants import (All2AllRProp, GDRProp,
                       ResizableAll2All)  # noqa
from .train_step import TrainStep  # noqa
from .standard_workflow import StandardWorkflow, parse_mcdnnic  # noqa
