"""mimo_v2: window and full attention layers mixed, a leading dense
feed-forward, then routed experts.

Fourth model family beside gpt2, llama and nemotron_h, after Xiaomi's
MiMo-V2 language models (`model_type` mimo_v2). Every block is
``x = x + attn(rmsnorm(x)); x = x + ffn(rmsnorm(x))``, and two tuples in
the config, one entry a layer, say which attention and which
feed-forward:

- `hybrid_layer_pattern` 0, a **full** layer: `num_attention_heads` query
  heads on `num_key_value_heads` KV heads, q and k heads `head_dim` wide
  and v heads `v_head_dim`, no bias, causal softmax over every earlier
  position, scores ``q.k / sqrt(head_dim)``;
- `hybrid_layer_pattern` 1, a **window** layer: the same with
  `swa_num_key_value_heads` KV heads; position t sees ``(t -
  sliding_window, t]``, itself included, and one learned scalar a query
  head, `sink`, joins the softmax as a column that has no value;
- in both, the first ``int(partial_rotary_factor * head_dim)`` dimensions
  of every q and k head are rotated (half-split pairs, `rope_theta` in a
  full layer, `swa_rope_theta` in a window layer), the others not; there
  is no q/k norm; and the attention's output is scaled by
  `attention_value_scale` (the published "v times the scale before the
  weighted sum", which is linear in v: scaled once after it here);
- `moe_layer_freq` 0, a **dense** SwiGLU feed-forward of
  `intermediate_size`; 1, **routed experts** (models/moe.py): a sigmoid
  router with a selection bias (`noaux_tc`; one group, so no group
  limit), `num_experts_per_tok` a token, their weights the scores without
  the bias normalised to one, SwiGLU experts of `moe_intermediate_size`,
  no shared expert. `experts_held` and `expert_offset` say which of the
  router's `n_routed_experts` this chip holds: it routes over all of them
  and computes its own experts' part of the result.

`attention_chunk_size` (equal to the window) is not read: the attention
as described needs no second meaning for it. The MTP layers and the
vision and audio towers are not part of this language model.

The pattern is a Python loop (each kind written once), the parameters one
dict a layer. Two kinds of KV layer (serve/llm/cache.py `KVKind`): the
forwards return k and v as ``(full layers', window layers')`` and take
their cached context as ``(full, window)``, each kind with its own KV
head count and, the window kind, its window and sinks. Matrix products
are in `dtype` (bf16: float32 accumulation on the MXU); the norms, the
rotation, the softmax and the router are float32.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any

import jax
import jax.numpy as jnp

from ray_tpu.models.moe import routed_experts
from ray_tpu.ops.context_attention import (
    attend_cached,
    causal_rows,
    softmax_over,
)
from ray_tpu.parallel.sharding import PartitionRules

Params = Any
FULL, WINDOW = 0, 1  # `hybrid_layer_pattern`'s two values
KIND_NAMES = ("full", "window")


@dataclasses.dataclass(frozen=True)
class MimoV2Config:
    """Field names are the published config.json's, but for the three
    that say what is held here and the seeded weights' spread."""

    vocab_size: int = 152576
    hidden_size: int = 4096
    hybrid_layer_pattern: tuple[int, ...] = (0, 1, 1, 1, 1, 0)
    moe_layer_freq: tuple[int, ...] = (0, 1, 1, 1, 1, 1)
    # attention
    num_attention_heads: int = 64
    num_key_value_heads: int = 4
    swa_num_key_value_heads: int = 8
    head_dim: int = 192
    v_head_dim: int = 128
    partial_rotary_factor: float = 0.334
    rope_theta: float = 1e7
    swa_rope_theta: float = 1e4
    sliding_window: int = 128
    attention_value_scale: float = 0.707
    add_swa_attention_sink_bias: bool = True
    add_full_attention_sink_bias: bool = False
    # feed-forward
    intermediate_size: int = 16384
    moe_intermediate_size: int = 2048
    n_routed_experts: int = 256
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = True
    routed_scaling_factor: float | None = None  # null as published: 1
    experts_held: int = 256  # of n_routed_experts, from expert_offset on
    expert_offset: int = 0
    layernorm_epsilon: float = 1e-5
    initializer_range: float = 0.02  # std of a seeded matrix
    max_position_embeddings: int = 1048576
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16  # what `init_mimo_v2` creates

    def __post_init__(self):
        if len(self.hybrid_layer_pattern) != len(self.moe_layer_freq):
            raise ValueError("hybrid_layer_pattern and moe_layer_freq "
                             "have one entry a layer each")
        if set(self.hybrid_layer_pattern) - {FULL, WINDOW} \
                or set(self.moe_layer_freq) - {0, 1}:
            raise ValueError("layer patterns hold 0 and 1 only")
        if self.expert_offset + self.experts_held > self.n_routed_experts:
            raise ValueError("experts held lie outside the router's range")

    # what the engine asks of every family's config
    @property
    def n_layer(self) -> int:
        return len(self.hybrid_layer_pattern)

    @property
    def block_size(self) -> int:
        return self.max_position_embeddings

    @property
    def padded_vocab(self) -> int:
        return ((self.vocab_size + 127) // 128) * 128

    @property
    def rotary_dim(self) -> int:
        return int(self.partial_rotary_factor * self.head_dim)

    def kv_heads(self, kind: int) -> int:
        return (self.swa_num_key_value_heads if kind == WINDOW
                else self.num_key_value_heads)

    def kv_kinds(self) -> tuple[tuple, ...]:
        """The two kinds of KV layer, each the fields of a
        serve/llm/cache.py `KVKind` (name, layers, KV heads, K and V head
        widths, window): the full layers first (their pool is the one
        `num_blocks` sizes)."""
        return tuple(
            (KIND_NAMES[kind], self.hybrid_layer_pattern.count(kind),
             self.kv_heads(kind), self.head_dim, self.v_head_dim,
             self.sliding_window if kind == WINDOW else None)
            for kind in (FULL, WINDOW))

    @staticmethod
    def tiny() -> "MimoV2Config":
        """Every layer kind at a size for CPU tests, float32: a window
        of 8, 16 experts of which 4 (from the 4th on) are held."""
        return MimoV2Config(
            vocab_size=512, hidden_size=64,
            hybrid_layer_pattern=(0, 1, 1, 0, 1),
            moe_layer_freq=(0, 1, 1, 1, 1), num_attention_heads=8,
            num_key_value_heads=2, swa_num_key_value_heads=4, head_dim=24,
            v_head_dim=16, sliding_window=8, intermediate_size=96,
            moe_intermediate_size=32, n_routed_experts=16,
            num_experts_per_tok=3, experts_held=4, expert_offset=4,
            max_position_embeddings=256, dtype=jnp.float32,
            param_dtype=jnp.float32)

    @staticmethod
    def v2_5() -> "MimoV2Config":
        """MiMo-V2.5's language model as published
        (huggingface.co/XiaomiMiMo/MiMo-V2.5, config.json): 48 blocks of
        4096, every expert held (620 GB in bf16: the base of the cut
        below, served nowhere here)."""
        return MimoV2Config(
            hybrid_layer_pattern=(0,) + (1, 1, 1, 1, 0) + (1, 1, 1, 1, 1, 0) * 7,
            moe_layer_freq=(0,) + (1,) * 47)

    @staticmethod
    def v2_5_l7_ep16() -> "MimoV2Config":
        """One chip's share where sixteen chips share each layer: the
        first 7 of 48 blocks (layer 0, full attention and the dense
        feed-forward, and one period: window x4, full, window), 16 of the
        256 experts and 19,072 of the 152,576 vocabulary rows; every
        width as published (PERF.md section 4)."""
        full = MimoV2Config.v2_5()
        return dataclasses.replace(
            full, hybrid_layer_pattern=full.hybrid_layer_pattern[:7],
            moe_layer_freq=full.moe_layer_freq[:7], experts_held=16,
            vocab_size=19072, max_position_embeddings=8704)


def mimo_v2_partition_rules() -> PartitionRules:
    """The held experts over `expert`; the vocabulary over `tensor`;
    attention, router and the dense feed-forward whole on every device, as
    the stated deployment has it."""
    from jax.sharding import PartitionSpec as P

    return PartitionRules([
        (r"layers/\d+/(we_gate|we_up|we_down)$", P("expert", None, None)),
        (r"wte$", P("tensor", None)),
        (r"lm_head$", P(None, "tensor")),
        (r".*", P()),
    ])


@functools.partial(jax.jit, static_argnames=("cfg",))
def init_mimo_v2(key: jax.Array, cfg: MimoV2Config) -> Params:
    """One program for the whole tree, every leaf drawn in float32 and
    written in `cfg.param_dtype` by the same fusion. Matrices are normal
    with std `initializer_range`, those that write the residual stream
    that over sqrt(L); norm scales 1. The sinks are normal with std 1
    (float32, as the softmax they join), so that they matter: a sink of 0
    against scores near 0 takes one share in window + 1. The router's
    selection bias is small noise (std 0.02), so that choosing (with it)
    and weighting (without) differ while the load stays near even."""
    L, D, V = cfg.n_layer, cfg.hidden_size, cfg.padded_vocab
    pdt = cfg.param_dtype
    std = cfg.initializer_range
    out_std = std / math.sqrt(L)
    k_wte, k_head, k_layers = jax.random.split(key, 3)

    def normal(k, shape, scale):
        return (jax.random.normal(k, shape, jnp.float32) * scale).astype(pdt)

    def experts(k, shape, scale):
        # drawn into place one expert at a time: no float32 copy of the
        # whole stack exists beside it
        keys = jax.random.split(k, shape[0])
        return jax.lax.fori_loop(
            0, shape[0],
            lambda i, buf: buf.at[i].set(normal(keys[i], shape[1:], scale)),
            jnp.zeros(shape, pdt))

    def attention(k, kind):
        ks = jax.random.split(k, 5)
        H, HK = cfg.num_attention_heads, cfg.kv_heads(kind)
        p = {
            "attn_norm": jnp.ones((D,), pdt),
            "wq": normal(ks[0], (D, H * cfg.head_dim), std),
            "wk": normal(ks[1], (D, HK * cfg.head_dim), std),
            "wv": normal(ks[2], (D, HK * cfg.v_head_dim), std),
            "wo": normal(ks[3], (H * cfg.v_head_dim, D), out_std),
        }
        if _has_sink(cfg, kind):
            p["sink"] = jax.random.normal(ks[4], (H,), jnp.float32)
        return p

    def feed_forward(k, routed):
        ks = jax.random.split(k, 5)
        if not routed:
            F = cfg.intermediate_size
            return {"ffn_norm": jnp.ones((D,), pdt),
                    "w_gate": normal(ks[0], (D, F), std),
                    "w_up": normal(ks[1], (D, F), std),
                    "w_down": normal(ks[2], (F, D), out_std)}
        X, F = cfg.experts_held, cfg.moe_intermediate_size
        return {"ffn_norm": jnp.ones((D,), pdt),
                "router": normal(ks[3], (D, cfg.n_routed_experts), std),
                "router_bias": normal(ks[4], (cfg.n_routed_experts,), 0.02),
                "we_gate": experts(ks[0], (X, D, F), std),
                "we_up": experts(ks[1], (X, D, F), std),
                "we_down": experts(ks[2], (X, F, D), out_std)}

    layers = []
    for kind, routed, k in zip(cfg.hybrid_layer_pattern, cfg.moe_layer_freq,
                               jax.random.split(k_layers, L)):
        ka, kf = jax.random.split(k)
        layers.append({**attention(ka, kind), **feed_forward(kf, routed)})
    return {"wte": normal(k_wte, (V, D), std), "layers": layers,
            "lnf": jnp.ones((D,), pdt),
            "lm_head": normal(k_head, (D, V), std)}


# --------------------------------------------------------------------------
# the two attention kinds and the two feed-forward kinds, each written once


def _has_sink(cfg: MimoV2Config, kind: int) -> bool:
    return (cfg.add_swa_attention_sink_bias if kind == WINDOW
            else cfg.add_full_attention_sink_bias)


def _rmsnorm(x, scale, eps):
    x32 = x.astype(jnp.float32)
    rms = jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (x32 * rms * scale.astype(jnp.float32)).astype(x.dtype)


def _rope(x, positions, theta: float, width: int):
    """The first `width` dimensions of every head of x (..., heads, D)
    rotated by `positions` (the leading dimensions'), half-split pairs
    ``(i, i + width / 2)``; the other dimensions as they are."""
    half = width // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    angles = positions.astype(jnp.float32)[..., None, None] * freqs
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = x[..., :half], x[..., half:width]
    return jnp.concatenate(
        [(x1 * cos - x2 * sin).astype(x.dtype),
         (x1 * sin + x2 * cos).astype(x.dtype), x[..., width:]], axis=-1)


def _qkv(h, p, positions, kind: int, cfg: MimoV2Config):
    """Normed rows h (..., D) at `positions` -> q (..., HK, R, hd) and k
    (..., HK, hd), both rotated, and v (..., HK, vd): the R query heads
    of a KV head side by side."""
    dt = cfg.dtype
    HK, hd = cfg.kv_heads(kind), cfg.head_dim
    theta = cfg.swa_rope_theta if kind == WINDOW else cfg.rope_theta
    lead = h.shape[:-1]
    q = (h @ p["wq"].astype(dt)).reshape(*lead, cfg.num_attention_heads, hd)
    k = (h @ p["wk"].astype(dt)).reshape(*lead, HK, hd)
    v = (h @ p["wv"].astype(dt)).reshape(*lead, HK, cfg.v_head_dim)
    q = _rope(q, positions, theta, cfg.rotary_dim)
    k = _rope(k, positions, theta, cfg.rotary_dim)
    return q.reshape(*lead, HK, cfg.num_attention_heads // HK, hd), k, v


def _sink(p, kind: int, cfg: MimoV2Config):
    """A layer's sinks (HK, R), or None where its kind has none."""
    if "sink" not in p:
        return None
    HK = cfg.kv_heads(kind)
    return p["sink"].reshape(HK, cfg.num_attention_heads // HK)


def _project(att, p, cfg: MimoV2Config):
    """att (B, T, HK, R, vd) -> (B, T, D): the value scale, then `wo`."""
    B, T = att.shape[:2]
    att = (att.astype(jnp.float32) * cfg.attention_value_scale).astype(
        cfg.dtype)
    return att.reshape(B, T, -1) @ p["wo"].astype(cfg.dtype)


def _dense(h, p, cfg: MimoV2Config):
    dt = cfg.dtype
    with jax.named_scope("ffn.dense"):
        return (jax.nn.silu(h @ p["w_gate"].astype(dt))
                * (h @ p["w_up"].astype(dt))) @ p["w_down"].astype(dt)


def _experts(h, p, cfg: MimoV2Config):
    """Normed rows h (N, D) -> (the held experts' part of the routed sum,
    pairs per expert over ALL experts)."""
    dt = cfg.dtype
    wg, wu, wd = (p[n].astype(dt) for n in ("we_gate", "we_up", "we_down"))
    y, counts, _ = routed_experts(
        h, p["router"],
        lambda a, mm: mm(jax.nn.silu(mm(a, wg)) * mm(a, wu), wd),
        k=cfg.num_experts_per_tok, norm_topk=cfg.norm_topk_prob,
        score="sigmoid", select_bias=p["router_bias"],
        scale=cfg.routed_scaling_factor or 1.0,
        held=(cfg.expert_offset, cfg.experts_held))
    return y, counts


def _stack(params, x, cfg: MimoV2Config, attention):
    """The blocks in the pattern's order on x (B, T, D) or (B, D).
    ``attention(h, p, kind, i) -> (out, k, v)`` is the program's way
    through a layer's attention, `i` counting the layers of its kind; the
    feed-forwards are the same in every program. Returns (logits f32, k
    and v as (full layers', window layers') stacks, pairs per expert
    stacked over the expert layers)."""
    eps = cfg.layernorm_epsilon
    ks, vs, counts = ([], []), ([], []), []
    for kind, routed, p in zip(cfg.hybrid_layer_pattern, cfg.moe_layer_freq,
                               params["layers"]):
        with jax.named_scope("attn." + KIND_NAMES[kind]):
            y, k, v = attention(_rmsnorm(x, p["attn_norm"], eps), p, kind,
                                len(ks[kind]))
        ks[kind].append(k)
        vs[kind].append(v)
        x = x + y
        h = _rmsnorm(x, p["ffn_norm"], eps)
        if routed:
            y, c = _experts(h.reshape(-1, h.shape[-1]), p, cfg)
            y = y.reshape(h.shape)
            counts.append(c)
        else:
            y = _dense(h, p, cfg)
        x = x + y
    x = _rmsnorm(x, params["lnf"], eps)
    logits = (x @ params["lm_head"].astype(cfg.dtype)).astype(jnp.float32)
    return (logits, tuple(jnp.stack(k) for k in ks),
            tuple(jnp.stack(v) for v in vs), jnp.stack(counts))


# --------------------------------------------------------------------------
# KV-cache inference steps (serve.llm): the model owns the mathematics,
# serve/llm/runner.py the pages.


def mimo_v2_prefill_kv(params: Params, tokens: jax.Array,
                       cfg: MimoV2Config):
    """A whole prompt from position 0: tokens (1, T) -> (logits (1, T,
    Vp) f32, k, v ((full layers, 1, T, HK, hd | vd), (window layers,
    ...)), pairs (expert layers, n_routed_experts))."""
    B, T = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(T), (B, T))
    ahead = jnp.arange(T)[:, None] - jnp.arange(T)[None, :]  # row - column
    seen = {FULL: (ahead >= 0)[None],
            WINDOW: ((ahead >= 0) & (ahead < cfg.sliding_window))[None]}

    def attention(h, p, kind, i):
        q, k, v = _qkv(h, p, positions, kind, cfg)
        att = softmax_over(q, [(k, v, seen[kind])],
                           1.0 / math.sqrt(cfg.head_dim), cfg.dtype,
                           sink=_sink(p, kind, cfg))
        return _project(att, p, cfg), k, v

    x = params["wte"].astype(cfg.dtype)[tokens]
    return _stack(params, x, cfg, attention)


def mimo_v2_prefill_chunk_kv(params: Params, tokens: jax.Array, start,
                             ctx, chunk_mask, cfg: MimoV2Config):
    """A chunk at positions start..start+T-1: ``ctx`` is the cached
    context (full kind's, window kind's) for positions < start."""
    B, T = tokens.shape
    positions = start + jnp.broadcast_to(jnp.arange(T), (B, T))
    own = causal_rows(chunk_mask)

    def attention(h, p, kind, i):
        q, k, v = _qkv(h, p, positions, kind, cfg)
        att = attend_cached(q, k, v, own, ctx[kind], i, cfg.dtype,
                            sink=_sink(p, kind, cfg))
        return _project(att, p, cfg), k, v

    x = params["wte"].astype(cfg.dtype)[tokens]
    return _stack(params, x, cfg, attention)


def mimo_v2_decode_kv(params: Params, tokens: jax.Array, positions, ctx,
                      cfg: MimoV2Config):
    """One token a lane: tokens (B,) at `positions`, against the lanes'
    cached context ``ctx`` (full, window) -> (logits (B, Vp) f32, k_new,
    v_new ((full layers, B, HK, hd | vd), (window layers, ...)),
    pairs)."""
    B = tokens.shape[0]
    own = jnp.ones((B, 1, 1), bool)

    def attention(h, p, kind, i):
        q, k, v = _qkv(h, p, positions, kind, cfg)
        att = attend_cached(q[:, None], k[:, None], v[:, None], own,
                            ctx[kind], i, cfg.dtype,
                            sink=_sink(p, kind, cfg))
        return _project(att, p, cfg)[:, 0], k, v

    x = params["wte"].astype(cfg.dtype)[tokens]
    return _stack(params, x, cfg, attention)
