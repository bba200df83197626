"""paddle_tpu.models: flagship model families beyond paddle.vision.

The reference ships its NLP models through PaddleNLP (ERNIE/BERT/GPT built on
python/paddle/nn/layer/transformer.py); this package provides the same model
families natively so BASELINE configs 3 and 5 (BERT finetune, GPT hybrid
parallel) are expressible inside the framework. ``lfm2`` is the LFM2-MoE
family (short convolutions, grouped-query rotary attention, sparse
experts) and ``sala`` is MiniCPM-SALA (block-sparse attention in a few
layers, decayed linear attention in the rest) and ``trinity`` is the Trinity
(``afmoe``) family (sliding-window and full attention layers mixed, sparse
experts beside a shared expert) and ``moonlight`` is the Moonlight
(``deepseek_v3``) family (multi-head latent attention, sparse experts beside
shared experts) and ``qwen3next`` is the Qwen3-Next (``qwen3_next``) family
(gated delta-rule linear attention in three layers of four, gated attention
with partial rotary in the fourth, softmax-routed experts beside a gated
shared expert), all five served on the paged engine.
"""
from .gpt import GPTConfig, GPTModel, GPTForCausalLM, GPTPretrainingCriterion  # noqa: F401
from .lfm2 import LFM2Config, LFM2ForCausalLM  # noqa: F401
from .sala import SALAConfig, MiniCPMSALAForCausalLM  # noqa: F401
from .trinity import TrinityConfig, TrinityForCausalLM  # noqa: F401
from .moonlight import MoonlightConfig, MoonlightForCausalLM  # noqa: F401
from .qwen3next import Qwen3NextConfig, Qwen3NextForCausalLM  # noqa: F401
from .bert import (BertConfig, BertModel,  # noqa: F401
                   BertForSequenceClassification,
                   ErnieConfig, ErnieModel,
                   ErnieForSequenceClassification)
