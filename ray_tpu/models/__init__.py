"""Model zoo for the TPU-native framework (pure-JAX, mesh-shardable):
GPT-2, Llama-family (RoPE/RMSNorm/SwiGLU/GQA), MoE layer. Eight families
are served (`serve/llm/runner.py` `adapters()`: gpt2, llama, nemotron_h,
mimo_v2, glm_dsa, lfm2, granite_hybrid, xing4); the six others are
imported where they are used, and `mamba2.py` and `mla.py` hold what two
of them share."""

from ray_tpu.models.gpt2 import (
    GPT2Config,
    gpt2_forward,
    gpt2_partition_rules,
    init_gpt2,
)
from ray_tpu.models.llama import (
    LlamaConfig,
    init_llama,
    llama_forward,
    llama_loss,
    llama_partition_rules,
)

__all__ = ["GPT2Config", "LlamaConfig", "gpt2_forward",
           "gpt2_partition_rules", "init_gpt2", "init_llama",
           "llama_forward", "llama_loss", "llama_partition_rules"]
