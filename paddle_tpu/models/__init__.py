"""Model zoo (reference: PaddleNLP model families + python/paddle/vision/models).

GPT is the flagship family — it is what the acceptance configs 3/4 train
(GPT-2 TP decode, GPT-3 6.7B hybrid; see BASELINE.md).
"""
from .gpt import GPTConfig, GPTModel, GPTForCausalLM, gpt2_small, gpt2_medium, gpt3_6p7b  # noqa: F401
from .bert import (  # noqa: F401
    BertConfig,
    BertForMaskedLM,
    BertModel,
    BertPretrainingCriterion,
)
from .llama import (  # noqa: F401
    LlamaConfig,
    LlamaForCausalLM,
    LlamaModel,
    llama2_7b,
    tiny_llama_config,
)
from .nemotron_h import NemotronHConfig, NemotronHForCausalLM  # noqa: F401
from .laguna import LagunaConfig, LagunaForCausalLM  # noqa: F401
from .phi4flash import Phi4FlashConfig, Phi4FlashForCausalLM  # noqa: F401
