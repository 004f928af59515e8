"""Model zoo + task registry — Flax replacement for ``modelling/``."""

from .registry import get_model_and_loss  # noqa: F401
from .resnet import ResNet, resnet18, resnet34, resnet50, resnet101, resnet152  # noqa: F401
from .tasks import Task, get_task, TASK_REGISTRY  # noqa: F401
from .transformer import (  # noqa: F401
    TransformerDecoder,
    TransformerEncoder,
    bert_base,
    bert_small,
    gpt_base,
    gpt_small,
    olmoe_1b_7b,
    olmoe_tiny,
)
from .clip import CLIP, clip_resnet50_bert, clip_tiny  # noqa: F401
from .vit import ViT, vit_base, vit_small, vit_tiny  # noqa: F401
