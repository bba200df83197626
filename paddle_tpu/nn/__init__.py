"""paddle.nn parity namespace (reference: python/paddle/nn/__init__.py)."""
from .layer_base import Layer, ParamAttr, HookRemoveHelper
from .container import Sequential, LayerList, LayerDict, ParameterList
from .layers_common import (
    Linear, Conv1D, Conv2D, Conv3D, Conv1DTranspose, Conv2DTranspose,
    Conv3DTranspose, BatchNorm, BatchNorm1D, BatchNorm2D, BatchNorm3D,
    SyncBatchNorm, LayerNorm, GroupNorm, InstanceNorm1D, InstanceNorm2D,
    InstanceNorm3D, LocalResponseNorm, SpectralNorm,
    MaxPool1D, MaxPool2D, MaxPool3D, AvgPool1D, AvgPool2D, AvgPool3D,
    AdaptiveAvgPool1D, AdaptiveAvgPool2D, AdaptiveAvgPool3D,
    AdaptiveMaxPool1D, AdaptiveMaxPool2D, AdaptiveMaxPool3D,
    Embedding, Dropout, Dropout2D, Dropout3D, AlphaDropout, Flatten,
    Upsample, UpsamplingBilinear2D, UpsamplingNearest2D, PixelShuffle,
    Pad1D, Pad2D, Pad3D, ZeroPad2D, CosineSimilarity, Bilinear, Unfold)
from .layers_activation import (
    ReLU, ReLU6, GELU, Sigmoid, Tanh, Softmax, LogSoftmax, LeakyReLU, ELU,
    SELU, CELU, Silu, Swish, Mish, Hardswish, Hardsigmoid, Hardtanh,
    Hardshrink, Softshrink, Tanhshrink, Softplus, Softsign, LogSigmoid,
    ThresholdedReLU, Maxout, PReLU, RReLU, GLU,
    CrossEntropyLoss, MSELoss, L1Loss, NLLLoss, BCELoss, BCEWithLogitsLoss,
    KLDivLoss, SmoothL1Loss, MarginRankingLoss, CTCLoss, CosineEmbeddingLoss,
    TripletMarginLoss, HSigmoidLoss, PairwiseDistance)
from .transformer import (MultiHeadAttention, TransformerEncoderLayer,
                          TransformerEncoder, TransformerDecoderLayer,
                          TransformerDecoder, Transformer, CAUSAL_MASK,
                          FLASH_CROSSOVER)
from .rnn import (RNNCellBase, SimpleRNNCell, LSTMCell, GRUCell, RNN,
                  SimpleRNN, LSTM, GRU, BiRNN)
from .moe import RMSNorm, ExpertStack, MoEFeedForward  # noqa: F401
from .beam_decode import BeamSearchDecoder, dynamic_decode  # noqa: F401
from .clip import (ClipGradByGlobalNorm, ClipGradByNorm,  # noqa: F401
                   ClipGradByValue)
from .utils_weight_norm import spectral_norm  # noqa: F401
from . import layers_activation as loss  # noqa: F401  (paddle.nn.loss)
from . import functional
from . import initializer
from .utils_weight_norm import weight_norm, remove_weight_norm, spectral_norm_fn

# paddle exposes utils under nn.utils
from . import utils  # noqa: F401
