"""gluon.model_zoo (parity: python/mxnet/gluon/model_zoo/__init__.py)."""
from . import vision
from . import transformer
from . import decoder
from .vision import get_model
from .transformer import TransformerLM, transformer_lm
from .decoder import DecoderLM
