"""Hot ops: attention (jnp reference + pallas TPU kernels), the loss
head's cross entropy, collective overlap helpers. The pallas kernels are
the TPU analogue of the reference's reliance on cuDNN/torch fused
kernels."""

from ray_tpu.ops.attention import causal_attention

__all__ = ["causal_attention"]
