"""Model zoo — the reference ships its flagship models via torchvision +
in-repo testing harnesses (examples/imagenet/main_amp.py:135,
apex/transformer/testing/standalone_gpt.py); here they are first-class."""

from beforeholiday_tpu.models import (
    deepseek_v3, keye_vl2, kimi_linear, lfm2_moe, mellum, nemotron_h, qwen3_next, resnet)
from beforeholiday_tpu.models.resnet import (
    CONFIGS,
    ResNetConfig,
    resnet18,
    resnet34,
    resnet50,
    resnet101,
    resnet152,
)

__all__ = [
    "deepseek_v3",
    "keye_vl2",
    "kimi_linear",
    "lfm2_moe",
    "mellum",
    "nemotron_h",
    "qwen3_next",
    "resnet",
    "CONFIGS",
    "ResNetConfig",
    "resnet18",
    "resnet34",
    "resnet50",
    "resnet101",
    "resnet152",
]
