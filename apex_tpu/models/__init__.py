"""apex_tpu.models — the benchmark model zoo.

The reference library ships no models (its examples pull torchvision);
this package provides the models its headline workloads train — ResNet for
``examples/imagenet`` (amp O2 + DDP + SyncBN), the MNIST MLP for
``examples/simple``, DCGAN for the multi-model/multi-optimizer exercise,
and a BERT encoder for the FusedLAMB + FusedLayerNorm config — all NHWC /
static-shape / bf16-friendly for TPU.
"""

from apex_tpu.models.mlp import MLP
from apex_tpu.models.resnet import (
    BasicBlock,
    Bottleneck,
    ResNet,
    ResNet18,
    ResNet34,
    ResNet50,
    ResNet101,
    ResNet152,
)
from apex_tpu.models.dcgan import Discriminator, Generator
from apex_tpu.models.gpt import (
    GPTConfig,
    GPTLMHeadModel,
    PipelinedGPT,
    gpt_medium,
    gpt_small,
    lm_loss,
)
from apex_tpu.models.moe import EP_RULES, MoEMlp, ep_rules
from apex_tpu.models.family import CacheRow
from apex_tpu.models.deepseek import DeepseekV3Config, DeepseekV3LMHeadModel
from apex_tpu.models.exaone_moe import ExaoneMoeConfig, ExaoneMoeLMHeadModel
from apex_tpu.models.lfm2_moe import Lfm2MoeConfig, Lfm2MoeLMHeadModel
from apex_tpu.models.bert import (
    BertConfig,
    BertEncoder,
    BertForPreTraining,
    PipelinedBert,
    bert_base,
    bert_large,
)

__all__ = [
    "BasicBlock",
    "CacheRow",
    "DeepseekV3Config",
    "DeepseekV3LMHeadModel",
    "EP_RULES",
    "ExaoneMoeConfig",
    "ExaoneMoeLMHeadModel",
    "GPTConfig",
    "GPTLMHeadModel",
    "Lfm2MoeConfig",
    "Lfm2MoeLMHeadModel",
    "PipelinedGPT",
    "gpt_medium",
    "gpt_small",
    "lm_loss",
    "MoEMlp",
    "ep_rules",
    "BertConfig",
    "BertEncoder",
    "BertForPreTraining",
    "PipelinedBert",
    "Bottleneck",
    "Discriminator",
    "Generator",
    "MLP",
    "ResNet",
    "ResNet18",
    "ResNet34",
    "ResNet50",
    "ResNet101",
    "ResNet152",
    "bert_base",
    "bert_large",
]
