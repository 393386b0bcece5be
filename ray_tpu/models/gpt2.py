"""GPT-2 in pure JAX, designed for mesh sharding.

This is the flagship Train model (reference benchmark: "TorchTrainer
GPT-2-small DDP", BASELINE.json). TPU-first design decisions:

- transformer blocks are *stacked* along a leading layer axis and executed
  with `lax.scan`: one compiled block body regardless of depth (fast
  compiles, XLA-friendly), instead of a Python loop of modules,
- parameters are a plain nested-dict pytree with declarative partition
  rules (ray_tpu.parallel.sharding) covering data/fsdp/tensor axes:
  Megatron-style column->row sharding inside attention and the MLP so the
  only tensor-axis collective per block is one psum (inserted by GSPMD),
- activations carry sharding constraints on the batch (data+fsdp) and
  hidden (tensor) dimensions,
- compute dtype bfloat16 (MXU-native), params float32.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ray_tpu.parallel.sharding import PartitionRules, constrain
from ray_tpu.ops.attention import causal_attention
from ray_tpu.ops.context_attention import attend_cached, causal_rows
from ray_tpu.ops.cross_entropy import cross_entropy

Params = Any


@dataclasses.dataclass(frozen=True)
class GPT2Config:
    vocab_size: int = 50257
    n_layer: int = 12
    n_head: int = 12
    n_embd: int = 768
    block_size: int = 1024
    dtype: Any = jnp.bfloat16
    # Pad the vocab so the logits matmul tiles cleanly onto the MXU and
    # shards evenly over the tensor axis (50257 -> 50304 for gpt2-small).
    vocab_pad_multiple: int = 128
    remat: bool = True

    @property
    def padded_vocab(self) -> int:
        m = self.vocab_pad_multiple
        return (self.vocab_size + m - 1) // m * m

    @property
    def head_dim(self) -> int:
        return self.n_embd // self.n_head

    @staticmethod
    def small() -> "GPT2Config":
        return GPT2Config()

    @staticmethod
    def medium() -> "GPT2Config":
        return GPT2Config(n_layer=24, n_head=16, n_embd=1024)

    @staticmethod
    def large() -> "GPT2Config":
        return GPT2Config(n_layer=36, n_head=20, n_embd=1280)

    @staticmethod
    def xl() -> "GPT2Config":
        return GPT2Config(n_layer=48, n_head=25, n_embd=1600)

    @staticmethod
    def tiny(vocab_size: int = 512, block_size: int = 128) -> "GPT2Config":
        return GPT2Config(
            vocab_size=vocab_size,
            n_layer=2,
            n_head=4,
            n_embd=128,
            block_size=block_size,
            vocab_pad_multiple=128,
        )


def gpt2_partition_rules() -> PartitionRules:
    """Megatron-style sharding. Stacked block params have a leading layer
    dim (None). Column-parallel: qkv / mlp fc shard output dim on
    'tensor'; row-parallel: attn proj / mlp proj shard input dim on
    'tensor'. 'fsdp' shards the other matmul dim (ZeRO-3-style)."""
    return PartitionRules(
        [
            (r"wte$", P("tensor", "fsdp")),
            (r"wpe$", P(None, "fsdp")),
            (r"attn_qkv/kernel$", P(None, "fsdp", "tensor")),
            (r"attn_proj/kernel$", P(None, "tensor", "fsdp")),
            (r"mlp_fc/kernel$", P(None, "fsdp", "tensor")),
            (r"mlp_proj/kernel$", P(None, "tensor", "fsdp")),
            (r"attn_qkv/bias$", P(None, "tensor")),
            (r"mlp_fc/bias$", P(None, "tensor")),
            # layer norms, row-parallel biases: replicated
            (r".*", P()),
        ]
    )


def gpt2_resident_params(params: Params, cfg: GPT2Config) -> Params:
    """The tree as a server holds it between programs: the leaves the
    forwards apply `.astype(cfg.dtype)` to (`wte`, `wpe`, every `kernel`
    and `bias` under `blocks`) in `cfg.dtype`, so that no program rounds
    them again; the layer-norm leaves (`ln1`, `ln2`, `lnf`) as given,
    because `_layer_norm` multiplies them in float32. A leaf already in
    `cfg.dtype` is returned as it is, the same buffer. The trainer keeps
    its float32 master copy and never calls this."""
    dt = jnp.dtype(cfg.dtype)

    def held(a):
        return a if a.dtype == dt else jnp.asarray(a, dt)

    blocks = {name: sub if name.startswith("ln") else jax.tree.map(held, sub)
              for name, sub in params["blocks"].items()}
    return {**params, "wte": held(params["wte"]),
            "wpe": held(params["wpe"]), "blocks": blocks}


@functools.partial(jax.jit, static_argnames=("cfg",))
def init_gpt2(key: jax.Array, cfg: GPT2Config) -> Params:
    """Initialize parameters (float32 master copy), GPT-2 init scheme:
    normal(0.02), residual projections scaled by 1/sqrt(2*n_layer). One
    program for the whole tree, as `init_llama` is: compiled once and
    found in the compile cache by the next process."""
    k = jax.random.split(key, 8)
    L, E, V = cfg.n_layer, cfg.n_embd, cfg.padded_vocab
    std = 0.02
    resid_std = 0.02 / math.sqrt(2 * cfg.n_layer)

    def normal(kk, shape, scale):
        # the draw and its scaling stay two roundings, as the eager calls
        # this program replaced made them: left side by side, XLA
        # multiplies `scale` into the sqrt(2) inside `normal` first, and a
        # quarter of the elements come out an ulp away
        # (tests/test_gpt2_init.py). Rounding to float32's own width
        # changes no value and keeps the two products apart, in the same
        # fusion; an `optimization_barrier` there splits it, and the
        # v5e's compiler then takes 40 s over this program where 8
        draw = jax.random.normal(kk, shape, jnp.float32)
        return jax.lax.reduce_precision(draw, 8, 23) * scale

    def stack(idx, shape, scale):
        # a layer a key, drawn into place one layer at a time (no `vmap`
        # over the keys: `init_llama`'s note)
        keys = jax.random.split(jax.random.fold_in(k[7], idx), L)
        return jax.lax.fori_loop(
            0, L, lambda i, buf: buf.at[i].set(normal(keys[i], shape, scale)),
            jnp.zeros((L,) + shape, jnp.float32))

    blocks = {
        "ln1": {"scale": jnp.ones((L, E)), "bias": jnp.zeros((L, E))},
        "attn_qkv": {"kernel": stack(0, (E, 3 * E), std),
                     "bias": jnp.zeros((L, 3 * E))},
        "attn_proj": {"kernel": stack(1, (E, E), resid_std),
                      "bias": jnp.zeros((L, E))},
        "ln2": {"scale": jnp.ones((L, E)), "bias": jnp.zeros((L, E))},
        "mlp_fc": {"kernel": stack(2, (E, 4 * E), std),
                   "bias": jnp.zeros((L, 4 * E))},
        "mlp_proj": {"kernel": stack(3, (4 * E, E), resid_std),
                     "bias": jnp.zeros((L, E))},
    }
    return {
        "wte": normal(k[0], (V, E), std),
        "wpe": normal(k[1], (cfg.block_size, E), std),
        "blocks": blocks,
        "lnf": {"scale": jnp.ones((E,)), "bias": jnp.zeros((E,))},
    }


def _layer_norm(x, scale, bias, eps=1e-5):
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return (y * scale + bias).astype(x.dtype)


def _block_kv(x, p, cfg: GPT2Config):
    """One transformer block. `p` holds this layer's (unstacked) params.
    Also returns this layer's attention K/V heads (B, T, H, D) so
    prefill (serve.llm) can seed a KV cache from the same math the
    training forward uses."""
    B, T, E = x.shape
    dt = cfg.dtype
    h = _layer_norm(x, p["ln1"]["scale"], p["ln1"]["bias"])
    qkv = h @ p["attn_qkv"]["kernel"].astype(dt) + p["attn_qkv"]["bias"].astype(dt)
    qkv = constrain(qkv, ("data", "fsdp"), None, "tensor")
    q, kk, v = jnp.split(qkv, 3, axis=-1)

    def heads(t):
        return t.reshape(B, T, cfg.n_head, cfg.head_dim)

    k_h, v_h = heads(kk), heads(v)
    att = causal_attention(heads(q), k_h, v_h)
    att = att.reshape(B, T, E)
    att = att @ p["attn_proj"]["kernel"].astype(dt) + p["attn_proj"]["bias"].astype(dt)
    x = x + constrain(att, ("data", "fsdp"), None, None)

    h = _layer_norm(x, p["ln2"]["scale"], p["ln2"]["bias"])
    h = h @ p["mlp_fc"]["kernel"].astype(dt) + p["mlp_fc"]["bias"].astype(dt)
    h = constrain(h, ("data", "fsdp"), None, "tensor")
    h = jax.nn.gelu(h)
    h = h @ p["mlp_proj"]["kernel"].astype(dt) + p["mlp_proj"]["bias"].astype(dt)
    x = x + constrain(h, ("data", "fsdp"), None, None)
    return x, (k_h, v_h)


def _block(x, p, cfg: GPT2Config):
    return _block_kv(x, p, cfg)[0]


def gpt2_forward(params: Params, tokens: jax.Array, cfg: GPT2Config) -> jax.Array:
    """tokens (B, T) int32 -> logits (B, T, padded_vocab) float32."""
    B, T = tokens.shape
    dt = cfg.dtype
    # The embedding table is vocab-sharded over 'tensor' (for the logits
    # matmul); a sharded gather would force XLA into an involuntary full
    # rematerialization, so explicitly all-gather it before the lookup
    # (it is small next to activations, and the transposed scatter-add in
    # backward then reduces cleanly).
    wte = constrain(params["wte"].astype(dt), None, None)
    x = wte[tokens] + params["wpe"].astype(dt)[:T]
    x = constrain(x, ("data", "fsdp"), None, None)

    block = _block
    if cfg.remat:
        # RAY_TPU_REMAT_POLICY selects what the backward replay reuses:
        # "full" (default) recomputes everything; "save_flash" keeps the
        # flash kernel's (o, lse); "save_dots" keeps all matmul outputs;
        # "none" disables remat.
        import os as _os

        # Default "full": at GPT-2-small's size the HBM traffic of
        # saving residuals cost more than the recompute's FLOPs when
        # the four policies were last compared on a v5e, which was
        # before PR 35 changed what the flash kernel saves (PERF.md §7
        # keeps the question open). Larger, activation-bound models
        # should flip to save_flash/save_dots via this env lever.
        mode = _os.environ.get("RAY_TPU_REMAT_POLICY", "full")
        if mode == "save_flash":
            policy = jax.checkpoint_policies.save_only_these_names(
                "flash_o", "flash_lse")
            block = jax.checkpoint(_block, static_argnums=(2,),
                                   policy=policy)
        elif mode == "save_dots":
            # save every matmul output AND the flash residuals: the
            # replay only redoes elementwise work (LN/gelu)
            policy = jax.checkpoint_policies.save_from_both_policies(
                jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
                jax.checkpoint_policies.save_only_these_names(
                    "flash_o", "flash_lse"))
            block = jax.checkpoint(_block, static_argnums=(2,),
                                   policy=policy)
        elif mode == "none":
            pass  # no remat: all activations saved
        else:  # "full": recompute everything
            block = jax.checkpoint(_block, static_argnums=(2,))

    def body(carry, layer_params):
        return block(carry, layer_params, cfg), None

    x, _ = jax.lax.scan(body, x, params["blocks"])
    x = _layer_norm(x, params["lnf"]["scale"], params["lnf"]["bias"])
    logits = x @ params["wte"].astype(dt).T
    logits = constrain(logits, ("data", "fsdp"), None, "tensor")
    return logits.astype(jnp.float32)


def gpt2_loss(params: Params, batch: dict, cfg: GPT2Config) -> jax.Array:
    """Next-token cross entropy; positions past vocab_size are masked."""
    logits = gpt2_forward(params, batch["tokens"], cfg)
    return cross_entropy(logits, batch["targets"],
                         vocab_size=cfg.vocab_size,
                         weights=batch.get("weights"))


# --------------------------------------------------------------------------
# KV-cache inference steps (serve.llm). Prefill runs the full-sequence
# forward and additionally returns every layer's K/V heads; decode runs
# ONE token per sequence against cached context K/V that each layer
# reads from ``ctx`` inside the layer scan, in tiles and to the lane's
# length (ops/context_attention.py; the page pool, its layout and the
# scatter of new rows belong to ray_tpu/serve/llm — the model layer only
# owns the math, so parity with the training forward is checkable
# function-against-function).


def gpt2_prefill_kv(
    params: Params, tokens: jax.Array, cfg: GPT2Config
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """tokens (B, T) -> (logits (B, T, Vp) f32, k, v (L, B, T, H, D))."""
    B, T = tokens.shape
    dt = cfg.dtype
    wte = constrain(params["wte"].astype(dt), None, None)
    x = wte[tokens] + params["wpe"].astype(dt)[:T]
    x = constrain(x, ("data", "fsdp"), None, None)

    def body(carry, layer_params):
        y, (k, v) = _block_kv(carry, layer_params, cfg)
        return y, (k, v)

    x, (k, v) = jax.lax.scan(body, x, params["blocks"])
    x = _layer_norm(x, params["lnf"]["scale"], params["lnf"]["bias"])
    logits = x @ params["wte"].astype(dt).T
    logits = constrain(logits, ("data", "fsdp"), None, "tensor")
    return logits.astype(jnp.float32), k, v


def _chunk_block(x, p, attend, cfg: GPT2Config):
    """Chunked-prefill block step. x (B, T, E) holds a CHUNK of the
    sequence at absolute positions start..start+T-1. Attention is the
    cached context + causal within the chunk, and is the caller's:
    ``attend(q, k, v) -> (B, T, H, D)`` (`_attend_cached`);
    projections/MLP are shared. Returns (x, (k, v)) with k/v (B, T, H, D) — the chunk's cache
    contribution."""
    B, T, E = x.shape
    dt = cfg.dtype
    H, D = cfg.n_head, cfg.head_dim
    h = _layer_norm(x, p["ln1"]["scale"], p["ln1"]["bias"])
    qkv = h @ p["attn_qkv"]["kernel"].astype(dt) + p["attn_qkv"]["bias"].astype(dt)
    qkv = constrain(qkv, ("data", "fsdp"), None, "tensor")
    q, k, v = (t.reshape(B, T, H, D) for t in jnp.split(qkv, 3, axis=-1))

    att = attend(q, k, v).reshape(B, T, E)
    att = att @ p["attn_proj"]["kernel"].astype(dt) + p["attn_proj"]["bias"].astype(dt)
    x = x + constrain(att, ("data", "fsdp"), None, None)

    h = _layer_norm(x, p["ln2"]["scale"], p["ln2"]["bias"])
    h = h @ p["mlp_fc"]["kernel"].astype(dt) + p["mlp_fc"]["bias"].astype(dt)
    h = constrain(h, ("data", "fsdp"), None, "tensor")
    h = jax.nn.gelu(h)
    h = h @ p["mlp_proj"]["kernel"].astype(dt) + p["mlp_proj"]["bias"].astype(dt)
    x = x + constrain(h, ("data", "fsdp"), None, None)
    return x, (k, v)


def _attend_cached(ctx, layer, own_valid, cfg: GPT2Config):
    """``attend(q, k, v)`` of the dense programs: q, k, v (B, T, H, D)
    against lane b's cached context ``ctx`` of layer `layer` (read in
    tiles, to the lane's length: ops/context_attention.py) and the
    program's own rows where `own_valid` (B, T, T) allows."""
    def attend(q, k, v):
        return attend_cached(q[:, :, :, None], k, v, own_valid, ctx, layer,
                             cfg.dtype)[:, :, :, 0]
    return attend


def gpt2_prefill_chunk_kv(
    params: Params,
    tokens: jax.Array,
    start: jax.Array,
    ctx,
    chunk_mask: jax.Array,
    cfg: GPT2Config,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Prefill a CHUNK of one or more sequences from a position offset
    (chunked / incremental prefill).

    tokens (B, T) sit at absolute positions start..start+T-1 (start is
    a traced scalar, so one compiled program serves every offset);
    ``ctx`` (an ops/context_attention.py `CachedContext`) is the cached
    context for positions < start, read layer by layer inside the layer
    scan (one layer's context exists at a time) and only as far as
    ``ctx.lengths`` reach; chunk_mask (B, T) marks the chunk's real
    tokens. Returns (logits (B, T, Vp) f32, k, v (L, B, T, H, D)) — the
    caller scatters k/v into the paged cache at the chunk's positions.
    """
    B, T = tokens.shape
    dt = cfg.dtype
    wte = constrain(params["wte"].astype(dt), None, None)
    # gather wpe by absolute position, NOT dynamic_slice: a slice clamps
    # its start when start+T overruns the table (bucket padding can push
    # past n_positions) and would silently shift every real token's
    # positional embedding. Only padded tail rows ever clip here, and
    # their K/V lands in the null page.
    pos = jnp.clip(start + jnp.arange(T), 0, cfg.block_size - 1)
    x = wte[tokens] + params["wpe"].astype(dt)[pos]
    x = constrain(x, ("data", "fsdp"), None, None)

    own_valid = causal_rows(chunk_mask)

    def body(carry, xs):
        p, layer = xs
        return _chunk_block(carry, p,
                            _attend_cached(ctx, layer, own_valid, cfg), cfg)

    x, (k, v) = jax.lax.scan(
        body, x, (params["blocks"], jnp.arange(cfg.n_layer)))
    x = _layer_norm(x, params["lnf"]["scale"], params["lnf"]["bias"])
    logits = x @ params["wte"].astype(dt).T
    logits = constrain(logits, ("data", "fsdp"), None, "tensor")
    return logits.astype(jnp.float32), k, v


def _decode_block(x, p, attend, cfg: GPT2Config):
    """Single-token block step. x (B, E); ``attend(q, k, v) -> (B, 1, H,
    D)`` on q, k, v (B, 1, H, D) is the cached context + the token itself
    (see `_chunk_block`). Returns (x, (k_new, v_new)) with k_new/v_new
    (B, H, D)."""
    B, E = x.shape
    dt = cfg.dtype
    H, D = cfg.n_head, cfg.head_dim
    h = _layer_norm(x, p["ln1"]["scale"], p["ln1"]["bias"])
    qkv = h @ p["attn_qkv"]["kernel"].astype(dt) + p["attn_qkv"]["bias"].astype(dt)
    qkv = constrain(qkv, ("data", "fsdp"), "tensor")
    q, k, v = (t.reshape(B, H, D) for t in jnp.split(qkv, 3, axis=-1))

    att = attend(q[:, None], k[:, None], v[:, None]).reshape(B, E)
    att = att @ p["attn_proj"]["kernel"].astype(dt) + p["attn_proj"]["bias"].astype(dt)
    x = x + constrain(att, ("data", "fsdp"), None)

    h = _layer_norm(x, p["ln2"]["scale"], p["ln2"]["bias"])
    h = h @ p["mlp_fc"]["kernel"].astype(dt) + p["mlp_fc"]["bias"].astype(dt)
    h = constrain(h, ("data", "fsdp"), "tensor")
    h = jax.nn.gelu(h)
    h = h @ p["mlp_proj"]["kernel"].astype(dt) + p["mlp_proj"]["bias"].astype(dt)
    x = x + constrain(h, ("data", "fsdp"), None)
    return x, (k, v)


def gpt2_decode_kv(
    params: Params,
    tokens: jax.Array,
    positions: jax.Array,
    ctx,
    cfg: GPT2Config,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """One decode step for a batch of sequences.

    tokens/positions (B,) i32; ``ctx`` is the lanes' cached context,
    ``ctx.lengths`` their positions (see gpt2_prefill_chunk_kv). Returns
    (logits (B, Vp) f32, k_new, v_new (L, B, H, D)) — the caller scatters
    k_new/v_new into the cache at each sequence's current position.
    """
    dt = cfg.dtype
    x = params["wte"].astype(dt)[tokens] + params["wpe"].astype(dt)[positions]

    own_valid = jnp.ones((tokens.shape[0], 1, 1), dtype=bool)

    def body(carry, xs):
        p, layer = xs
        return _decode_block(carry, p,
                             _attend_cached(ctx, layer, own_valid, cfg), cfg)

    x, (k_new, v_new) = jax.lax.scan(
        body, x, (params["blocks"], jnp.arange(cfg.n_layer)))
    x = _layer_norm(x, params["lnf"]["scale"], params["lnf"]["bias"])
    logits = x @ params["wte"].astype(dt).T
    return logits.astype(jnp.float32), k_new, v_new


def count_params(params: Params) -> int:
    return sum(int(x.size) for x in jax.tree_util.tree_leaves(params))
