"""byteps_tpu.models — model zoo for benchmarks and examples.

The reference ships no models of its own (SURVEY §1: "models come from the
host framework") — its examples train torchvision/keras models. A
standalone TPU framework needs its own: these functional JAX models are the
benchmark and test workloads (BASELINE configs: ResNet-50, BERT, GPT-2) and
the flagship for the driver's compile checks.
"""

from byteps_tpu.models.gpt import (GPTConfig, gpt_init, gpt_forward,
                                   gpt_hidden, gpt_loss, gpt_pp_loss)
from byteps_tpu.models.gpt import gpt_param_specs
from byteps_tpu.models.generate import (
    KVCache, gpt_apply_cached, init_cache, make_generate_fn,
)
from byteps_tpu.models.bert import (
    BertConfig, bert_init, bert_forward, bert_hidden, bert_mlm_loss,
    bert_param_specs,
)
from byteps_tpu.models.joyai import JoyAIConfig, joyai_init, joyai_loss
from byteps_tpu.models.moe_gpt import (
    MoEGPTConfig, moe_gpt_init, moe_gpt_loss, moe_gpt_param_specs,
    moe_gpt_pp_loss,
)
from byteps_tpu.models.t5 import (
    T5Config, t5_init, t5_forward, t5_encode, t5_decode, t5_loss,
    t5_param_specs, synthetic_seq2seq_batch,
    T5DecCache, t5_init_cache, t5_cross_kv, t5_decode_cached,
    make_t5_generate_fn,
)
from byteps_tpu.models.vit import (
    ViTConfig, vit_init, vit_forward, vit_loss, vit_param_specs,
    synthetic_vit_batch,
)
from byteps_tpu.models.resnet import (
    ResNetConfig, resnet_init, resnet_forward, resnet_loss,
    resnet_param_specs,
)

__all__ = [
    "GPTConfig", "gpt_init", "gpt_forward", "gpt_hidden", "gpt_loss",
    "gpt_pp_loss", "gpt_param_specs",
    "KVCache", "gpt_apply_cached", "init_cache", "make_generate_fn",
    "BertConfig", "bert_init", "bert_forward", "bert_hidden",
    "bert_mlm_loss", "bert_param_specs",
    "JoyAIConfig", "joyai_init", "joyai_loss",
    "MoEGPTConfig", "moe_gpt_init", "moe_gpt_loss", "moe_gpt_param_specs",
    "moe_gpt_pp_loss",
    "ResNetConfig", "resnet_init", "resnet_forward", "resnet_loss",
    "resnet_param_specs",
    "T5Config", "t5_init", "t5_forward", "t5_encode", "t5_decode",
    "t5_loss", "t5_param_specs", "synthetic_seq2seq_batch",
    "T5DecCache", "t5_init_cache", "t5_cross_kv", "t5_decode_cached",
    "make_t5_generate_fn",
    "ViTConfig", "vit_init", "vit_forward", "vit_loss",
    "vit_param_specs", "synthetic_vit_batch",
]
