"""Model zoo — the five BASELINE.json workload families, flax-native.

Reference analog: per-script raw-TF model fns (SURVEY.md §2a). Each module
ships the flax Module, a config dataclass, and analytic FLOPs for MFU
accounting (utils/flops.py)."""

from . import common  # noqa: F401
from .mlp import MLP, MLPConfig  # noqa: F401
from .cnn import CNN, CNNConfig  # noqa: F401
from .resnet import ResNet, ResNet50, ResNetConfig  # noqa: F401
from .wide_deep import WideDeep, WideDeepConfig  # noqa: F401
from .olmo_hybrid import OlmoHybrid, OlmoHybridConfig  # noqa: F401
from .transformer import (  # noqa: F401
    Transformer,
    TransformerConfig,
    bert_base,
    gpt_small,
)
