from .bert import BertModel, BertConfig, BertForPretraining  # noqa: F401
from .gpt import GPTModel, GPTConfig  # noqa: F401
from .gpt import GPTMoEModel, GPTMoEConfig  # noqa: F401
from .latent_moe import LatentMoEDecoder, LatentMoEConfig  # noqa: F401
from .hybrid_conv import HybridConvDecoder, HybridConvConfig  # noqa: F401
