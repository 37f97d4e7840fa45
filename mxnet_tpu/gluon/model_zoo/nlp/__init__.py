"""NLP model zoo — GluonNLP-capability models, TPU-first.

Reference capability: the GluonNLP model zoo consumed through the Gluon API
(SURVEY.md §1 L8: "GluonCV / GluonNLP are separate repos consuming the
Gluon API", named in BASELINE.json configs 2-4). Families here:

* Transformer NMT (`get_transformer`, capability: transformer_en_de_512)
* BERT (`bert_12_768_12`, `bert_24_1024_16`)
* Llama-style decoder LM (`llama_3_8b` — stretch config, new capability)
* MoE expert-parallel FFN (`MoEMLP`, GShard-style — the `ep` mesh axis)
* LongCat-Flash (`LongcatFlashModel`: latent attention, shortcut-connected
  routed experts of which a chip holds a share, zero-compute experts)
* GLM-5 (`GlmDsaModel`: latent attention over a learned selection of the
  cached tokens, sigmoid-routed experts with a shared expert)
* Phi-4-mini-flash (`Phi4FlashModel`: Mamba-1 + sliding-window layers, one
  shared K/V cache read by cross-attention, gated memory units)
* Falcon-H1 (`FalconH1Model`: parallel hybrid blocks, a Mamba-2 / SSD mixer
  and GQA attention side by side in every layer, muP multipliers)
* dots.vlm1 (`DotsVlmModel`: a NaViT vision tower in front of a
  DeepSeek-V3-shaped decoder: dense latent attention with YaRN rotary,
  group-limited sigmoid routing, a shared expert)
* SDAR-MoE (`SdarMoeModel`: a Qwen3-MoE-shaped decoder, every routed expert
  held, that generates by diffusion over blocks of positions under a
  block-causal mask)
* Ling-3.0-flash (`LingLinearModel`: Kimi Delta Attention layers, a
  delta-rule matrix state a head with a decay a key channel, beside one
  latent-attention layer in six, group-limited sigmoid experts)

Each family ships Megatron-style tensor-parallel ShardingRules
(`*_sharding_rules`) consumed by mxnet_tpu.parallel.TrainStep.
"""
from .attention import MultiHeadAttention
from .transformer import (PositionwiseFFN, TransformerEncoderCell,
                          TransformerDecoderCell, TransformerEncoder,
                          TransformerDecoder, Transformer, get_transformer,
                          transformer_sharding_rules)
from .bert import (BERTEncoder, BERTModel, bert_12_768_12, bert_24_1024_16,
                   bert_sharding_rules)
from .llama import (RMSNorm, LlamaAttention, LlamaMLP, LlamaBlock,
                    LlamaModel, llama_tiny, llama_3_8b,
                    llama_sharding_rules, LlamaModelPP, llama_tiny_pp,
                    llama_pp_sharding_rules)
from .moe import MoEMLP, moe_sharding_rules
from .longcat_flash import (LongcatFFN, LongcatMLA, LongcatMoE,
                            LongcatDoubleLayer, LongcatFlashModel,
                            longcat_flash_tiny)
from .glm_moe_dsa import (GlmDsaAttention, GlmDsaMoE, GlmDsaLayer,
                          GlmDsaModel, glm_moe_dsa_tiny)
from .phi4flash import (Phi4FlashMamba, Phi4FlashAttention,
                        Phi4FlashCrossAttention, Phi4FlashGMU,
                        Phi4FlashLayer, Phi4FlashModel, phi4flash_tiny)
from .dots_vlm import (DotsMLA, DotsVlmLayer, DotsVlmModel, dots_vlm_tiny)
from .falcon_h1 import (FalconH1Mamba2, FalconH1Attention, FalconH1MLP,
                        FalconH1Layer, FalconH1Model, falcon_h1_tiny)
from .sdar_moe import (SdarAttention, SdarMoE, SdarMoeLayer, SdarMoeModel,
                       sdar_moe_tiny)
from .ling_linear import (LingKDA, LingMLA, LingLayer, LingLinearModel,
                          ling_linear_tiny)

_models = {
    "transformer": get_transformer,
    "bert_12_768_12": bert_12_768_12,
    "bert_24_1024_16": bert_24_1024_16,
    "llama_tiny": llama_tiny,
    "llama_3_8b": llama_3_8b,
    "llama_tiny_pp": llama_tiny_pp,
    "longcat_flash_tiny": longcat_flash_tiny,
    "glm_moe_dsa_tiny": glm_moe_dsa_tiny,
    "phi4flash_tiny": phi4flash_tiny,
    "falcon_h1_tiny": falcon_h1_tiny,
    "dots_vlm_tiny": dots_vlm_tiny,
    "sdar_moe_tiny": sdar_moe_tiny,
    "ling_linear_tiny": ling_linear_tiny,
}


def get_model(name, **kwargs):
    """reference surface: gluonnlp.model.get_model(name)."""
    name = str(name).lower()
    if name not in _models:
        raise ValueError(
            f"unknown nlp model {name!r}; available: {sorted(_models)}")
    return _models[name](**kwargs)
