"""Model families: Llama (flagship), GPT, ERNIE, Granite-4.0-H (Mamba-2 +
attention hybrid), DeepSeek-V3 (latent attention + routed experts),
Nemotron-H (one mixer a block: Mamba-2, attention or routed experts), Ouro
(one stack of layers run several times). Vision models live in
paddle_tpu.vision.models."""

from .llama import (LlamaConfig, LlamaForCausalLM, LlamaModel, llama2_7b, llama2_13b,  # noqa: F401
                    llama2_70b, llama_moe_tiny, llama_tiny, mixtral_8x7b)
from .gpt import GPTConfig, GPTForCausalLM, GPTModel, gpt2_small, gpt3_1p3b, gpt_tiny  # noqa: F401
from .ernie import (ErnieConfig, ErnieForMaskedLM, ErnieForSequenceClassification,  # noqa: F401
                    ErnieModel, ernie3_base, ernie_tiny)
from .serve_protocol import (AttentionLayer, LatentAttentionLayer,  # noqa: F401
                             StateLayer, StatelessLayer)
from .granite_hybrid import (GraniteHybridConfig, GraniteHybridForCausalLM,  # noqa: F401
                             GraniteHybridModel, granite_hybrid_tiny)
from .deepseek_v3 import (DeepseekV3Config, DeepseekV3ForCausalLM,  # noqa: F401
                          DeepseekV3Model, deepseek_v3_tiny)
from .nemotron_h import (NemotronHConfig, NemotronHForCausalLM,  # noqa: F401
                         NemotronHModel, nemotron_h_tiny)
from .ouro import (OuroConfig, OuroForCausalLM, OuroModel,  # noqa: F401
                   ouro_tiny)
