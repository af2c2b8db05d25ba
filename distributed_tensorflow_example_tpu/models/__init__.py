"""Model zoo, in the order of the reference's workload configs
(BASELINE.json:7-11): MNIST MLP, MNIST LeNet CNN, CIFAR ResNet-20,
ImageNet ResNet-50, BERT-base MLM.
"""

from .base import Model, get_model, list_models, register_model
from . import mlp as mlp          # registers "mlp"
from . import lenet as lenet      # registers "lenet"
from . import resnet as resnet    # registers "resnet20", "resnet50"
from . import bert as bert        # registers "bert", "bert_tiny"
from . import moe as moe          # registers "moe_bert", "moe_bert_tiny"
from . import pipe_mlp as pipe_mlp  # registers "pipe_mlp"
from . import pipe_bert as pipe_bert  # registers "pipe_bert"(+_tiny)
from . import pipe_moe as pipe_moe  # registers "pipe_moe_bert"(+_tiny)
from . import gpt as gpt          # registers "gpt", "gpt_tiny"
from . import decoder as decoder  # registers "sdar_moe"(+_tiny)

__all__ = ["Model", "get_model", "list_models", "register_model"]
