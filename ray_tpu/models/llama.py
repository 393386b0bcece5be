"""Llama-family transformer — RMSNorm, RoPE, SwiGLU, grouped-query attn.

Second flagship model family beside GPT-2 (SURVEY.md §2.4 model breadth;
the reference trains Llama-class models through TorchTrainer — here the
architecture is built TPU-first like models/gpt2.py): scan-stacked
blocks, Megatron-sharded partition rules over the canonical mesh axes,
bf16 activations with f32 norms, flash attention via ops.attention, and
GQA (n_kv_heads < n_heads) with K/V head replication at attention time.

The OLMoE block is the same block with three departures, each a config
field: the feed-forward half is a router and `n_experts` routed SwiGLU
experts of width `intermediate`, `n_experts_per_tok` a token and none
dropped (models/moe.py); q and k are RMS-normalised over their whole
projection before the heads are split and rotated (`qk_norm`); the output
head is a matrix of its own (`tie_embeddings` False). Every forward below
is built from three helpers — `_qkv`, `_ffn`, `_head` — so a layer kind is
written once.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any

import jax
import jax.numpy as jnp

from ray_tpu.models.moe import routed_experts
from ray_tpu.ops.attention import causal_attention
from ray_tpu.ops.context_attention import attend_cached, causal_rows
from ray_tpu.ops.cross_entropy import cross_entropy
from ray_tpu.parallel.sharding import PartitionRules, constrain

Params = Any


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    n_layer: int = 8
    n_head: int = 8
    n_kv_head: int = 4  # grouped-query attention
    n_embd: int = 512
    intermediate: int = 1408  # SwiGLU hidden (~8/3 * n_embd, 128-aligned)
    block_size: int = 1024
    rope_theta: float = 10000.0
    rms_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    remat: bool = True
    n_experts: int = 0  # 0: dense SwiGLU; else routed experts
    n_experts_per_tok: int = 0
    norm_topk_prob: bool = False  # renormalise the chosen experts' weights
    qk_norm: bool = False  # RMSNorm over the whole q and k projections
    tie_embeddings: bool = True  # False: the head is `lm_head`, not wte.T
    param_dtype: Any = jnp.float32  # what `init_llama` creates leaves in

    @property
    def head_dim(self) -> int:
        return self.n_embd // self.n_head

    @property
    def padded_vocab(self) -> int:
        return ((self.vocab_size + 127) // 128) * 128

    @staticmethod
    def tiny() -> "LlamaConfig":
        return LlamaConfig(vocab_size=512, n_layer=2, n_head=4, n_kv_head=2,
                           n_embd=128, intermediate=384, block_size=128,
                           dtype=jnp.float32, remat=False)

    @staticmethod
    def small() -> "LlamaConfig":
        """~110M-param config comparable to GPT-2-small for benching."""
        return LlamaConfig(vocab_size=32000, n_layer=12, n_head=12,
                           n_kv_head=4, n_embd=768, intermediate=2048,
                           block_size=1024)

    @staticmethod
    def olmoe_tiny() -> "LlamaConfig":
        """The OLMoE block at a size for CPU tests, float32."""
        return LlamaConfig(vocab_size=512, n_layer=2, n_head=4, n_kv_head=4,
                           n_embd=128, intermediate=64, block_size=128,
                           dtype=jnp.float32, remat=False, n_experts=8,
                           n_experts_per_tok=2, qk_norm=True,
                           tie_embeddings=False)

    @staticmethod
    def olmoe_1b_7b() -> "LlamaConfig":
        """OLMoE-1B-7B-0125-Instruct as published (huggingface.co/allenai/
        OLMoE-1B-7B-0125-Instruct, config.json): 16 x 2048, 16 heads of
        128, 64 experts of 1024 with 8 a token, softmax over all 64 and no
        renormalisation, bf16 weights."""
        return LlamaConfig(vocab_size=50304, n_layer=16, n_head=16,
                           n_kv_head=16, n_embd=2048, intermediate=1024,
                           block_size=4096, rope_theta=10000.0,
                           rms_eps=1e-5, remat=False, n_experts=64,
                           n_experts_per_tok=8, norm_topk_prob=False,
                           qk_norm=True, tie_embeddings=False,
                           param_dtype=jnp.bfloat16)

    @staticmethod
    def olmoe_1b_7b_l8() -> "LlamaConfig":
        """The published widths at 8 of 16 layers: what one 16 GB chip
        holds twice over while weights are swapped (PERF.md)."""
        return dataclasses.replace(LlamaConfig.olmoe_1b_7b(), n_layer=8)


def llama_partition_rules() -> PartitionRules:
    """Megatron layout over the canonical axes: attention/MLP input
    projections sharded on the output dim over 'tensor', output
    projections on the input dim; embeddings vocab-sharded; everything
    fsdp-sharded on the other dim."""
    from jax.sharding import PartitionSpec as P

    # block params are scan-STACKED: leading dim is the layer axis and
    # must stay unsharded (None), like gpt2_partition_rules
    return PartitionRules([
        (r"blocks/(wq|wk|wv)$", P(None, "fsdp", "tensor")),
        (r"blocks/wo$", P(None, "tensor", "fsdp")),
        (r"blocks/(w_gate|w_up)$", P(None, "fsdp", "tensor")),
        (r"blocks/w_down$", P(None, "tensor", "fsdp")),
        # routed experts are stacked (L, E, in, out): E on `expert`
        (r"blocks/(we_gate|we_up)$", P(None, "expert", "fsdp", "tensor")),
        (r"blocks/we_down$", P(None, "expert", "tensor", "fsdp")),
        (r"blocks/(ln_attn|ln_mlp)$", P()),
        (r"wte$", P("tensor", "fsdp")),
        (r"lm_head$", P("fsdp", "tensor")),
        (r"lnf$", P()),
        (r".*", P()),
    ])


@functools.partial(jax.jit, static_argnames=("cfg",))
def init_llama(key: jax.Array, cfg: LlamaConfig) -> Params:
    """One program for the whole tree (compiled once, found in the compile
    cache by the next process), every leaf drawn in float32 and written in
    `cfg.param_dtype` by the same fusion: a float32 copy of a leaf never
    exists (at OLMoE's sizes it would not fit beside a resident tree)."""
    L, E, V = cfg.n_layer, cfg.n_embd, cfg.padded_vocab
    hd = cfg.head_dim
    kv_dim = cfg.n_kv_head * hd
    pdt = cfg.param_dtype
    std = 0.02
    out_std = std / math.sqrt(2 * L)
    ks = jax.random.split(key, 8)  # the dense tree's keys, as ever
    k_router, k_head = jax.random.split(jax.random.fold_in(key, 8))

    def normal(k, shape, scale):
        return (jax.random.normal(k, shape, jnp.float32) * scale).astype(pdt)

    def stack(base, shape, scale):
        # a layer a key, as ever, drawn into place one layer at a time: a
        # `vmap` over the keys costs 1.1 GB of temporaries at OLMoE's
        # sizes, this loop none (compiled for the v5e, PR 27)
        keys = jax.random.split(base, L)
        return jax.lax.fori_loop(
            0, L, lambda i, buf: buf.at[i].set(normal(keys[i], shape, scale)),
            jnp.zeros((L,) + shape, pdt))

    F = cfg.intermediate
    blocks = {
        "ln_attn": jnp.ones((L, E), pdt),
        "wq": stack(ks[1], (E, E), std),
        "wk": stack(ks[2], (E, kv_dim), std),
        "wv": stack(ks[3], (E, kv_dim), std),
        "wo": stack(ks[4], (E, E), out_std),
        "ln_mlp": jnp.ones((L, E), pdt),
    }
    if cfg.n_experts:
        X = cfg.n_experts
        blocks["router"] = stack(k_router, (E, X), std)
        blocks["we_gate"] = stack(ks[5], (X, E, F), std)
        blocks["we_up"] = stack(ks[6], (X, E, F), std)
        blocks["we_down"] = stack(ks[7], (X, F, E), out_std)
    else:
        blocks["w_gate"] = stack(ks[5], (E, F), std)
        blocks["w_up"] = stack(ks[6], (E, F), std)
        blocks["w_down"] = stack(ks[7], (F, E), out_std)
    if cfg.qk_norm:
        blocks["q_norm"] = jnp.ones((L, E), pdt)
        blocks["k_norm"] = jnp.ones((L, kv_dim), pdt)
    params = {"wte": normal(ks[0], (V, E), std), "blocks": blocks,
              "lnf": jnp.ones((E,), pdt)}
    if not cfg.tie_embeddings:
        params["lm_head"] = normal(k_head, (E, V), std)
    return params


def _rmsnorm(x, scale, eps):
    x32 = x.astype(jnp.float32)
    rms = jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (x32 * rms * scale).astype(x.dtype)


def _rope(x, theta: float):
    """Rotary embedding over the last dim of (B, T, H, D)."""
    B, T, H, D = x.shape
    half = D // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    angles = jnp.arange(T, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos = jnp.cos(angles)[None, :, None, :]
    sin = jnp.sin(angles)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    rotated = jnp.concatenate(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return rotated.astype(x.dtype)


def _rope_at(x, positions, theta: float):
    """Rotary embedding for single-token decode: x (B, H, D) rotated by
    each sequence's absolute position (B,). Same formula as `_rope`, so
    cached prefill K and decode K agree bit-for-bit per position."""
    B, H, D = x.shape
    half = D // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    angles = positions.astype(jnp.float32)[:, None] * freqs[None, :]
    cos = jnp.cos(angles)[:, None, :]
    sin = jnp.sin(angles)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    rotated = jnp.concatenate(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return rotated.astype(x.dtype)


def _qkv(h, p, cfg: LlamaConfig):
    """Attention projections of normed activations h (..., E): q
    (..., H, D), k and v (..., HK, D), before rotation. With `qk_norm`, q
    and k are RMS-normalised over their whole projection first."""
    dt = cfg.dtype
    q = h @ p["wq"].astype(dt)
    k = h @ p["wk"].astype(dt)
    v = h @ p["wv"].astype(dt)
    if cfg.qk_norm:
        q = _rmsnorm(q, p["q_norm"], cfg.rms_eps)
        k = _rmsnorm(k, p["k_norm"], cfg.rms_eps)
    hd = cfg.head_dim
    return (q.reshape(*h.shape[:-1], cfg.n_head, hd),
            k.reshape(*h.shape[:-1], cfg.n_kv_head, hd),
            v.reshape(*h.shape[:-1], cfg.n_kv_head, hd))


def _ffn(h, p, cfg: LlamaConfig):
    """The feed-forward half on normed activations h (..., E): dense
    SwiGLU, or a router and routed SwiGLU experts. Returns (y, pairs per
    expert (n_experts,) i32, or None for a dense block)."""
    dt = cfg.dtype
    rows = (("data", "fsdp"),) + (None,) * (h.ndim - 2)
    if not cfg.n_experts:
        gate = constrain(h @ p["w_gate"].astype(dt), *rows, "tensor")
        y = (jax.nn.silu(gate) * (h @ p["w_up"].astype(dt))) \
            @ p["w_down"].astype(dt)
        return constrain(y, *rows, None), None
    wg, wu, wd = (p[n].astype(dt) for n in ("we_gate", "we_up", "we_down"))
    y, counts, _ = routed_experts(
        h.reshape(-1, h.shape[-1]), p["router"],
        lambda a, mm: mm(jax.nn.silu(mm(a, wg)) * mm(a, wu), wd),
        k=cfg.n_experts_per_tok, norm_topk=cfg.norm_topk_prob)
    return constrain(y.reshape(h.shape), *rows, None), counts


def _head(x, params, cfg: LlamaConfig):
    """Final norm and output head: x (..., E) -> logits (..., Vp) f32."""
    x = _rmsnorm(x, params["lnf"], cfg.rms_eps)
    w = params["wte"].astype(cfg.dtype).T if cfg.tie_embeddings \
        else params["lm_head"].astype(cfg.dtype)
    return (x @ w).astype(jnp.float32)


def _aux(counts) -> tuple:
    """What a forward returns after (logits, k, v): the routed blocks'
    pairs per layer and expert (L, n_experts), nothing for a dense model."""
    return () if counts is None else (counts,)


def _block_kv(x, p, cfg: LlamaConfig):
    """One block; also returns post-rope, pre-GQA-replication K/V heads
    (B, T, H_kv, D) — the layout serve.llm caches (decode replicates at
    attention time, like the forward path) — and the routed experts' pair
    counts (None for a dense block)."""
    B, T, E = x.shape
    dt = cfg.dtype

    q, k, v = _qkv(_rmsnorm(x, p["ln_attn"], cfg.rms_eps), p, cfg)
    q = _rope(q, cfg.rope_theta)
    k = _rope(k, cfg.rope_theta)
    k_cache, v_cache = k, v
    # GQA: replicate K/V heads up to n_head (reference semantics of
    # repeat_kv; XLA turns the broadcast into reuse, no materialized copy
    # survives fusion)
    rep = cfg.n_head // cfg.n_kv_head
    k = jnp.repeat(k, rep, axis=2)
    v = jnp.repeat(v, rep, axis=2)
    att = causal_attention(q, k, v).reshape(B, T, E)
    att = att @ p["wo"].astype(dt)
    x = x + constrain(att, ("data", "fsdp"), None, None)

    y, counts = _ffn(_rmsnorm(x, p["ln_mlp"], cfg.rms_eps), p, cfg)
    return x + y, (k_cache, v_cache, counts)


def _block(x, p, cfg: LlamaConfig):
    return _block_kv(x, p, cfg)[0]


def llama_forward(params: Params, tokens: jax.Array,
                  cfg: LlamaConfig) -> jax.Array:
    """tokens (B, T) int32 -> logits (B, T, padded_vocab) float32."""
    B, T = tokens.shape
    dt = cfg.dtype
    wte = constrain(params["wte"].astype(dt), None, None)
    x = wte[tokens]
    x = constrain(x, ("data", "fsdp"), None, None)

    block = _block
    if cfg.remat:
        block = jax.checkpoint(_block, static_argnums=(2,))

    def body(carry, layer_params):
        return block(carry, layer_params, cfg), None

    x, _ = jax.lax.scan(body, x, params["blocks"])
    return constrain(_head(x, params, cfg), ("data", "fsdp"), None, "tensor")


# --------------------------------------------------------------------------
# KV-cache inference steps (serve.llm) — see models/gpt2.py for the
# layering contract: models own the math, serve/llm/runner.py owns the
# paged gather/scatter. K is cached POST-rope with n_kv_head heads.


def llama_prefill_kv(
    params: Params, tokens: jax.Array, cfg: LlamaConfig
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """tokens (B, T) -> (logits (B, T, Vp) f32, k, v (L, B, T, Hkv, D)),
    and for routed blocks the pairs per layer and expert (`_aux`), as
    every forward below."""
    dt = cfg.dtype
    wte = constrain(params["wte"].astype(dt), None, None)
    x = wte[tokens]
    x = constrain(x, ("data", "fsdp"), None, None)

    x, (k, v, counts) = jax.lax.scan(
        lambda carry, p: _block_kv(carry, p, cfg), x, params["blocks"])
    logits = constrain(_head(x, params, cfg), ("data", "fsdp"), None,
                       "tensor")
    return (logits, k, v) + _aux(counts)


def _rope_chunk(x, start, theta: float):
    """Rotary embedding for a chunk at absolute positions
    start..start+T-1 (start traced): x (B, T, H, D). Same formula as
    `_rope`/`_rope_at`, so chunked K agrees bit-for-bit per position."""
    B, T, H, D = x.shape
    half = D // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    pos = (start + jnp.arange(T)).astype(jnp.float32)
    angles = pos[:, None] * freqs[None, :]
    cos = jnp.cos(angles)[None, :, None, :]
    sin = jnp.sin(angles)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    rotated = jnp.concatenate(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return rotated.astype(x.dtype)


def _attend_cached(ctx, layer, own_valid, cfg: LlamaConfig):
    """``attend(q, k, v)`` of the dense programs: q (B, T, H, D), k and v
    (B, T, Hkv, D) post-rope, against lane b's cached context ``ctx`` of
    layer `layer` (read in tiles, to the lane's length:
    ops/context_attention.py) and the program's own rows where
    `own_valid` (B, T, T) allows. Query head h reads KV head ``h //
    (H // Hkv)``; K and V are never repeated."""
    def attend(q, k, v):
        B, T, H, D = q.shape
        HK = cfg.n_kv_head
        return attend_cached(q.reshape(B, T, HK, H // HK, D), k, v,
                             own_valid, ctx, layer, cfg.dtype
                             ).reshape(B, T, H, D)
    return attend


def _chunk_block(x, p, attend, start, cfg: LlamaConfig):
    """Chunked-prefill block step; see models/gpt2.py `_chunk_block`.
    x (B, T, E) at absolute positions start..start+T-1. Returns (x, (k,
    v)) with k/v (B, T, Hkv, D) post-rope, pre-GQA-replication — the
    cached layout. ``attend(q, k, v) -> (B, T, H, D)`` (k/v
    pre-replication) is the cached context + causal within the chunk
    (`_attend_cached`, which does the GQA head mapping itself)."""
    B, T, E = x.shape
    dt = cfg.dtype

    q, k, v = _qkv(_rmsnorm(x, p["ln_attn"], cfg.rms_eps), p, cfg)
    q = _rope_chunk(q, start, cfg.rope_theta)
    k = _rope_chunk(k, start, cfg.rope_theta)

    att = attend(q, k, v).reshape(B, T, E) @ p["wo"].astype(dt)
    x = x + constrain(att, ("data", "fsdp"), None, None)

    y, counts = _ffn(_rmsnorm(x, p["ln_mlp"], cfg.rms_eps), p, cfg)
    return x + y, (k, v, counts)


def llama_prefill_chunk_kv(
    params: Params,
    tokens: jax.Array,
    start: jax.Array,
    ctx,
    chunk_mask: jax.Array,
    cfg: LlamaConfig,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Chunked prefill from a position offset; see gpt2_prefill_chunk_kv.
    ``ctx`` is the cached context (rows (Hkv, D), post-rope); returns
    (logits (B, T, Vp) f32, k, v (L, B, T, Hkv, D))."""
    dt = cfg.dtype
    wte = constrain(params["wte"].astype(dt), None, None)
    x = wte[tokens]
    x = constrain(x, ("data", "fsdp"), None, None)
    own_valid = causal_rows(chunk_mask)

    def body(carry, xs):
        p, layer = xs
        return _chunk_block(
            carry, p, _attend_cached(ctx, layer, own_valid, cfg), start, cfg)

    x, (k, v, counts) = jax.lax.scan(
        body, x, (params["blocks"], jnp.arange(cfg.n_layer)))
    logits = constrain(_head(x, params, cfg), ("data", "fsdp"), None,
                       "tensor")
    return (logits, k, v) + _aux(counts)


def _decode_block(x, p, attend, positions, cfg: LlamaConfig):
    """Single-token block step; x (B, E), positions (B,). Returns (x,
    (k_new, v_new)) with k_new/v_new (B, Hkv, D). ``attend(q, k, v) ->
    (B, 1, H, D)`` on q (B, 1, H, D), k/v (B, 1, Hkv, D) is the cached
    context + the token itself (see `_chunk_block`)."""
    B, E = x.shape
    dt = cfg.dtype

    q, k, v = _qkv(_rmsnorm(x, p["ln_attn"], cfg.rms_eps), p, cfg)
    q = _rope_at(q, positions, cfg.rope_theta)
    k = _rope_at(k, positions, cfg.rope_theta)

    att = attend(q[:, None], k[:, None], v[:, None]).reshape(B, E) \
        @ p["wo"].astype(dt)
    x = x + constrain(att, ("data", "fsdp"), None)

    y, counts = _ffn(_rmsnorm(x, p["ln_mlp"], cfg.rms_eps), p, cfg)
    return x + y, (k, v, counts)


def llama_decode_kv(
    params: Params,
    tokens: jax.Array,
    positions: jax.Array,
    ctx,
    cfg: LlamaConfig,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """One decode step; see gpt2_decode_kv. ``ctx`` is the lanes' cached
    context; returns (logits (B, Vp) f32, k_new, v_new (L, B, Hkv, D))."""
    dt = cfg.dtype
    x = params["wte"].astype(dt)[tokens]
    own_valid = jnp.ones((tokens.shape[0], 1, 1), dtype=bool)

    def body(carry, xs):
        p, layer = xs
        return _decode_block(
            carry, p, _attend_cached(ctx, layer, own_valid, cfg), positions,
            cfg)

    x, (k_new, v_new, counts) = jax.lax.scan(
        body, x, (params["blocks"], jnp.arange(cfg.n_layer)))
    return (_head(x, params, cfg), k_new, v_new) + _aux(counts)


def llama_loss(params: Params, batch: dict, cfg: LlamaConfig) -> jax.Array:
    logits = llama_forward(params, batch["tokens"], cfg)
    return cross_entropy(logits, batch["targets"],
                         vocab_size=cfg.vocab_size)
