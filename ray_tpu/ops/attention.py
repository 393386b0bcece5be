"""Causal multi-head attention.

Two paths:
- `causal_attention_reference`: plain jnp einsum formulation — XLA fuses
  this well and it runs on any backend (CPU tests, interpret mode).
- `flash_attention`: pallas TPU kernel (ray_tpu.ops.flash_attention) with
  online softmax, used on TPU for long sequences: it reads and writes
  `(B, T, H, D)` where it lies (two 64-wide heads a 128-lane block, or one
  head of a multiple of 128), computes no score tile above the diagonal
  and masks only the tiles the diagonal crosses.

Softmax statistics are computed in float32 regardless of input dtype
(bfloat16 accumulation loses too much precision on long sequences).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ray_tpu.parallel.ops import shard_map
from ray_tpu.parallel.sharding import _current_mesh, _prune_spec

# Sequence length at or above which the pallas kernel pays for itself.
_FLASH_MIN_SEQ = 512
# The kernel's smallest block: T must divide into blocks of this size.
_FLASH_BLOCK_MIN = 128


def causal_attention_reference(
    q: jax.Array, k: jax.Array, v: jax.Array
) -> jax.Array:
    """q,k,v: (B, T, H, D) -> (B, T, H, D), causal."""
    B, T, H, D = q.shape
    scale = 1.0 / (D**0.5)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    mask = jnp.tril(jnp.ones((T, T), dtype=bool))
    logits = jnp.where(mask, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def causal_attention(q: jax.Array, k: jax.Array, v: jax.Array) -> jax.Array:
    """Dispatch on platform and shape: the pallas flash kernel on TPU for
    sequences long enough to pay for it and divisible into its blocks,
    the einsum everywhere else. The choice is made before the kernel is
    called; a kernel that fails to trace, lower or compile raises."""
    T = q.shape[1]
    if (jax.default_backend() != "tpu" or T < _FLASH_MIN_SEQ
            or T % _FLASH_BLOCK_MIN):
        return causal_attention_reference(q, k, v)
    return sharded_flash_attention(q, k, v, _current_mesh())


def sharded_flash_attention(q, k, v, mesh, *, interpret: bool = False):
    """The flash kernel under `mesh` (None: one device). GSPMD cannot
    partition a Mosaic kernel, so on a mesh it runs per shard inside a
    shard_map — batch over (data, fsdp), heads over tensor, the layout
    the models constrain q/k/v to. Attention mixes neither batch rows
    nor heads, so the shards need no collective. The kernel picks its
    layout from the shape it sees there, a shard's: an even count of
    64-wide heads a shard is paired, an odd one takes the per-head path
    (`flash_attention.plan`)."""
    from ray_tpu.ops.flash_attention import flash_attention

    kernel = functools.partial(flash_attention, causal=True,
                               interpret=interpret)
    spec = (_prune_spec(P(("data", "fsdp"), None, "tensor", None), mesh)
            if mesh is not None else P())
    if not any(spec):
        return kernel(q, k, v)
    return shard_map(kernel, mesh, in_specs=(spec, spec, spec),
                     out_specs=spec)(q, k, v)
