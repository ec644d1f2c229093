"""paddle_tpu.models — reference model families (flagship: Llama).

Coverage of the bench.py training configs: Llama (TP/PP/CP hybrid trainers),
Ouro (looped Llama-style blocks, served by the same decoder),
AFMoE (routed experts as one chip's share, gated window/full attention,
served by the same decoder),
EvaByte (EVA chunked linearized attention: a window leaf and a summary leaf
a cache layer, served by the same decoder),
GPT (fused-qkv causal LM), BERT (MLM pretraining), diffusion UNet
(SD-style), plus vision CNNs in paddle_tpu.vision.models.
"""

from paddle_tpu.models.llama import (  # noqa: F401
    LLAMA_7B_CONFIG, TINY_CONFIG, LlamaConfig, LlamaForCausalLM, LlamaModel,
    llama_tp_plan,
)
from paddle_tpu.models.ouro import (  # noqa: F401
    OURO_TINY, OuroConfig, OuroForCausalLM, OuroModel,
)
from paddle_tpu.models.afmoe import (  # noqa: F401
    AFMOE_TINY, AfmoeConfig, AfmoeForCausalLM, AfmoeModel,
)
from paddle_tpu.models.evabyte import (  # noqa: F401
    EVABYTE_TINY, EvabyteConfig, EvabyteForCausalLM, EvabyteModel,
)
from paddle_tpu.models.gpt import GPT_TINY, GPTConfig, GPTForCausalLM  # noqa: F401
from paddle_tpu.models.bert import BERT_TINY, BertConfig, BertForMaskedLM  # noqa: F401
from paddle_tpu.models.unet import UNET_TINY, UNet2DConditionModel, UNetConfig  # noqa: F401
