"""paddle.nn parity surface."""
from . import functional  # noqa: F401
from . import initializer  # noqa: F401
from .clip import (  # noqa: F401
    ClipGradByValue, ClipGradByNorm, ClipGradByGlobalNorm, clip_grad_norm_,
)
from .layer.layers import Layer, ParamAttr  # noqa: F401
from .layer.container import (  # noqa: F401
    Sequential, LayerList, ParameterList, LayerDict,
)
from .layer.common import (  # noqa: F401
    Linear, Dropout, Dropout2D, Dropout3D, AlphaDropout, Embedding, Flatten,
    Upsample, UpsamplingBilinear2D, UpsamplingNearest2D, Pad1D, Pad2D, Pad3D,
    ZeroPad2D, CosineSimilarity, PairwiseDistance, Bilinear, PixelShuffle,
    Unfold, Identity,
)
from .layer.conv import (  # noqa: F401
    Conv1D, Conv2D, Conv3D, Conv1DTranspose, Conv2DTranspose, Conv3DTranspose,
)
from .layer.norm import (  # noqa: F401
    BatchNorm, BatchNorm1D, BatchNorm2D, BatchNorm3D, SyncBatchNorm,
    LayerNorm, GroupNorm, InstanceNorm1D, InstanceNorm2D, InstanceNorm3D,
    LocalResponseNorm, SpectralNorm,
)
from .layer.pooling import (  # noqa: F401
    MaxPool1D, MaxPool2D, MaxPool3D, AvgPool1D, AvgPool2D, AvgPool3D,
    AdaptiveAvgPool1D, AdaptiveAvgPool2D, AdaptiveAvgPool3D,
    AdaptiveMaxPool1D, AdaptiveMaxPool2D, AdaptiveMaxPool3D,
)
from .layer.activation import (  # noqa: F401
    ReLU, ReLU6, GELU, Sigmoid, Tanh, Silu, Mish, Hardswish, Hardsigmoid,
    Softsign, Tanhshrink, LogSigmoid, LeakyReLU, ELU, CELU, SELU, Hardtanh,
    Hardshrink, Softshrink, Softplus, ThresholdedReLU, PReLU, RReLU, Softmax,
    LogSoftmax, Maxout, Swish,
)
from .layer.loss import (  # noqa: F401
    CrossEntropyLoss, MSELoss, L1Loss, NLLLoss, BCELoss, BCEWithLogitsLoss,
    KLDivLoss, SmoothL1Loss, HuberLoss, MarginRankingLoss, CTCLoss,
    CosineEmbeddingLoss, TripletMarginLoss,
)
from .layer.transformer import (  # noqa: F401
    MultiHeadAttention, TransformerEncoderLayer, TransformerEncoder,
    TransformerDecoderLayer, TransformerDecoder, Transformer,
)
from .layer.moe import (  # noqa: F401
    MoELayer, MoEEncoderLayer, ExpertFFN, DroplessMoE, SwiGLU,
)
from .layer.latent_attention import (  # noqa: F401
    LatentAttention, RMSNorm,
)
from .layer.rnn import (  # noqa: F401
    RNNCellBase, SimpleRNNCell, LSTMCell, LSTMPCell, GRUCell, RNN, BiRNN, SimpleRNN,
    LSTM, GRU,
)
from .layer.loss import HSigmoidLoss  # noqa: F401
from .decode import BeamSearchDecoder, dynamic_decode  # noqa: F401
from . import utils  # noqa: F401
from . import decode  # noqa: F401
# reference exposes the layer submodules under paddle.nn too
from .layer import (  # noqa: F401
    common, conv, loss, norm, rnn,
)
