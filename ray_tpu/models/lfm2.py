"""lfm2: blocks whose operator is a gated short convolution or attention.

Sixth served family, after Liquid AI's LFM2 mixture-of-experts models
(`model_type` lfm2_moe). Every block is

    h = x + op(rmsnorm(x));  y = h + ffn(rmsnorm(h))

with `layer_types` saying which `op` a block has and `num_dense_layers`
which `ffn`:

- ``conv``, a gated short convolution: ``B | C | X = in_proj(u)`` (no
  bias, in that order), ``g = B * X``, a depthwise causal convolution of
  `conv_L_cache` rows over g with no bias and no activation
  (``c_t = sum_j w_j g_{t-2+j}``, zeros before the sequence's first row),
  ``out_proj(C * c)``;
- ``full_attention``: causal softmax attention with grouped K and V heads
  and no bias, q and k RMS-normalised a head BEFORE the rotation, rotary
  position embedding over the whole head (half-split pairs);
- the first `num_dense_layers` blocks a SwiGLU ``w2(silu(w1 h) * w3 h)``;
- every later block routed SwiGLU experts (models/moe.py): a sigmoid
  router, the `num_experts_per_tok` largest of score + `expert_bias`
  chosen, weighted by the scores WITHOUT the bias over their sum + 1e-6,
  none shared. `experts_held` and `expert_offset` say which of the
  router's `num_experts` this chip holds: it routes over all of them and
  computes its own experts' part of the result.

After the last block `embedding_norm`, then the head, which is the
embedding (tied). `layer_types` has no clean period (its tail is
irregular), so the stack is a Python loop over the blocks, each kind
written once, and the parameters are one dict a layer.

Two kinds of cached state (serve/llm/cache.py): the attention layers' K
and V in pages (`n_kv_layers` of them), and a conv layer's recurrent
state a lane slot: the last ``conv_L_cache - 1`` rows of g, a part each,
in `dtype`. A prompt or a chunk starts from the lane's carried rows
(zeros on a sequence's first rows) and leaves the rows after its last
REAL row; a decode step moves every owned slot's window one row on, and
a slot no lane of the step owns is written back as read.

Matrix products are in `dtype` (bf16: float32 accumulation on the MXU);
the convolution's sum, the norms, the softmax and the router are float32.
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

CONV, ATTENTION = "conv", "full_attention"
# LFM2-8B-A1B's `layer_types`: 18 conv and 6 attention blocks
_LAYERS_8B_A1B = tuple(
    ATTENTION if i in (2, 6, 10, 14, 18, 21) else CONV for i in range(24))


@dataclasses.dataclass(frozen=True)
class Lfm2Config:
    """Field names are the published config.json's, but for `head_dim`
    (the config has no key: hidden_size / num_attention_heads) and the
    two that say what is held here."""

    vocab_size: int = 65536
    hidden_size: int = 2048
    layer_types: tuple = _LAYERS_8B_A1B
    # conv
    conv_L_cache: int = 3
    # full_attention
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    head_dim: int = 64
    rope_theta: float = 1e6
    # feed-forward
    num_dense_layers: int = 2
    intermediate_size: int = 7168
    moe_intermediate_size: int = 1792
    num_experts: int = 32
    num_experts_per_tok: int = 4
    norm_topk_prob: bool = True
    use_expert_bias: bool = True
    routed_scaling_factor: float = 1.0
    experts_held: int = 32  # of num_experts, from expert_offset on
    expert_offset: int = 0
    norm_eps: float = 1e-5
    initializer_range: float = 0.02  # std of a seeded matrix
    max_position_embeddings: int = 128000
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16  # what `init_lfm2` creates

    def __post_init__(self):
        if set(self.layer_types) - {CONV, ATTENTION}:
            raise ValueError(f"layer_types {self.layer_types!r}: only "
                             f"{CONV!r} and {ATTENTION!r} are layer kinds")
        if self.expert_offset + self.experts_held > self.num_experts:
            raise ValueError("experts held lie outside the router's range")

    # what the engine asks of every family's config
    @property
    def n_layer(self) -> int:
        return len(self.layer_types)

    @property
    def block_size(self) -> int:
        return self.max_position_embeddings

    @property
    def padded_vocab(self) -> int:
        return ((self.vocab_size + 127) // 128) * 128

    @property
    def n_kv_layers(self) -> int:
        return self.layer_types.count(ATTENTION)

    @property
    def n_conv_layers(self) -> int:
        return self.layer_types.count(CONV)

    @property
    def n_expert_layers(self) -> int:
        return self.n_layer - self.num_dense_layers

    def state_parts(self) -> tuple:
        """(name, shape a lane and layer, dtype) of a conv layer's
        recurrent state, for `cache.StateLayout`: the window a part a row
        (`conv0` the oldest), so that each buffer is (layers, slots,
        hidden) and tiles without padding (see
        `NemotronHConfig.state_parts`)."""
        return tuple((f"conv{j}", (self.hidden_size,), self.dtype)
                     for j in range(self.conv_L_cache - 1))

    @staticmethod
    def tiny() -> "Lfm2Config":
        """All four layer kinds at a size for CPU tests, float32: 8
        experts of which 4 (from the 2nd on) are held."""
        return Lfm2Config(
            vocab_size=512, hidden_size=64,
            layer_types=(CONV, CONV, ATTENTION, CONV, CONV, ATTENTION, CONV),
            num_attention_heads=4, num_key_value_heads=2, head_dim=16,
            num_dense_layers=2, intermediate_size=96,
            moe_intermediate_size=32, num_experts=8, num_experts_per_tok=2,
            experts_held=4, expert_offset=2, max_position_embeddings=256,
            dtype=jnp.float32, param_dtype=jnp.float32)

    @staticmethod
    def lfm2_8b_a1b() -> "Lfm2Config":
        """LFM2-8B-A1B as published (huggingface.co/LiquidAI/LFM2-8B-A1B,
        config.json): 24 blocks of 2048, every expert held (16.7 GB in
        bf16: the base of the cut below, served nowhere here)."""
        return Lfm2Config()

    @staticmethod
    def lfm2_8b_a1b_ep4() -> "Lfm2Config":
        """One chip's share where the four chips of a v5e host share each
        layer: all 24 blocks, 8 of the 32 experts and 16,384 of the 65,536
        vocabulary rows; every width as published (PERF.md section 4)."""
        return dataclasses.replace(
            Lfm2Config.lfm2_8b_a1b(), experts_held=8, vocab_size=16384,
            max_position_embeddings=8576)


def lfm2_partition_rules() -> PartitionRules:
    """The held experts over `expert`; the vocabulary over `tensor`;
    everything else (operators, dense SwiGLUs, routers) whole on every
    device, as the stated deployment has it."""
    from jax.sharding import PartitionSpec as P

    return PartitionRules([
        (r"layers/\d+/(we_gate|we_up|we_down)$", P("expert", None, None)),
        (r"wte$", P("tensor", None)),
        (r".*", P()),
    ])


@functools.partial(jax.jit, static_argnames=("cfg",))
def init_lfm2(key: jax.Array, cfg: Lfm2Config) -> Params:
    """One program for the whole tree, every leaf drawn in float32 and
    written in `cfg.param_dtype` by the same fusion. Matrices are normal
    with std `initializer_range`, those that write the residual stream
    that over sqrt(L); norm scales 1; the conv as torch's Conv1d default
    (uniform within 1 / sqrt(conv_L_cache)). The router's selection bias
    (float32, as the scores it joins) is small noise (std 0.02), so that
    choosing (with it) and weighting (without) differ while the load
    stays near even."""
    L, D, V = cfg.n_layer, cfg.hidden_size, cfg.padded_vocab
    pdt = cfg.param_dtype
    std = cfg.initializer_range
    out_std = std / math.sqrt(L)
    k_wte, k_layers = jax.random.split(key)

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

    def conv(k):
        ks = jax.random.split(k, 3)
        K = cfg.conv_L_cache
        bound = 1.0 / math.sqrt(K)
        return {
            "operator_norm": jnp.ones((D,), pdt),
            "in_proj": normal(ks[0], (D, 3 * D), std),
            "conv_w": jax.random.uniform(
                ks[1], (K, D), jnp.float32, -bound, bound).astype(pdt),
            "out_proj": normal(ks[2], (D, D), out_std),
        }

    def attention(k):
        ks = jax.random.split(k, 4)
        hd = cfg.head_dim
        q_dim = cfg.num_attention_heads * hd
        kv_dim = cfg.num_key_value_heads * hd
        return {
            "operator_norm": jnp.ones((D,), pdt),
            "wq": normal(ks[0], (D, q_dim), std),
            "wk": normal(ks[1], (D, kv_dim), std),
            "wv": normal(ks[2], (D, kv_dim), std),
            "wo": normal(ks[3], (q_dim, D), out_std),
            "q_norm": jnp.ones((hd,), pdt),
            "k_norm": jnp.ones((hd,), pdt),
        }

    def feed_forward(k, routed):
        ks = jax.random.split(k, 5)
        if not routed:
            F = cfg.intermediate_size
            return {"ffn_norm": jnp.ones((D,), pdt),
                    "w1": normal(ks[0], (D, F), std),
                    "w3": normal(ks[1], (D, F), std),
                    "w2": normal(ks[2], (F, D), out_std)}
        X, F = cfg.experts_held, cfg.moe_intermediate_size
        return {"ffn_norm": jnp.ones((D,), pdt),
                "router": normal(ks[3], (D, cfg.num_experts), std),
                "expert_bias": jax.random.normal(
                    ks[4], (cfg.num_experts,), jnp.float32) * 0.02,
                "we_gate": experts(ks[0], (X, D, F), std),
                "we_up": experts(ks[1], (X, D, F), std),
                "we_down": experts(ks[2], (X, F, D), out_std)}

    make = {CONV: conv, ATTENTION: attention}
    layers = []
    for i, (kind, k) in enumerate(zip(cfg.layer_types,
                                      jax.random.split(k_layers, L))):
        ko, kf = jax.random.split(k)
        layers.append({**make[kind](ko),
                       **feed_forward(kf, i >= cfg.num_dense_layers)})
    return {"wte": normal(k_wte, (V, D), std), "layers": layers,
            "embedding_norm": jnp.ones((D,), pdt)}


# --------------------------------------------------------------------------
# the two operators and the two feed-forwards, each written once


def _rmsnorm(x, scale, eps):
    x32 = x.astype(jnp.float32)
    rms = jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (x32 * rms * scale.astype(jnp.float32)).astype(x.dtype)


def _conv_inputs(h, p, cfg: Lfm2Config):
    """Normed rows h (..., D) -> the gate's product g = B * X and C, both
    (..., D) in `dtype`."""
    with jax.named_scope("conv.in_proj"):
        bcx = h @ p["in_proj"].astype(cfg.dtype)
    with jax.named_scope("conv.gate"):
        B, C, X = jnp.split(bcx, 3, axis=-1)
        return B * X, C


def _conv_output(c, C, p, cfg: Lfm2Config):
    """The convolution's sum c (..., D) f32 under its gate C -> (..., D)
    after `out_proj`."""
    with jax.named_scope("conv.out_proj"):
        y = (C.astype(jnp.float32) * c).astype(cfg.dtype)
        return y @ p["out_proj"].astype(cfg.dtype)


def _conv_rows(h, p, cfg: Lfm2Config, view, index: int, n_valid):
    """The conv operator on one lane's normed rows h (T, D), from the
    window in the lane's slot (zero on a sequence's first rows) and
    leaving there the last rows of g up to row ``n_valid - 1``."""
    T = h.shape[0]
    K = cfg.conv_L_cache
    g, C = _conv_inputs(h, p, cfg)
    state = view.lane(index)
    with jax.named_scope("conv.window"):
        # seen[j] is g K-1-j rows before the program's first; rows of the
        # lane's earlier programs come from its slot
        seen = jnp.concatenate(
            [jnp.stack([state[f"conv{j}"] for j in range(K - 1)]).astype(
                g.dtype), g])
        w = p["conv_w"].astype(jnp.float32)
        c = sum(w[j] * seen[j:j + T].astype(jnp.float32) for j in range(K))
        # the last K-1 REAL rows: padded rows leave the window alone
        window = jax.lax.dynamic_slice_in_dim(seen, n_valid, K - 1)
        view.set_lane(index, {f"conv{j}": window[j] for j in range(K - 1)})
    return _conv_output(c, C, p, cfg)


def _conv_step(h, p, cfg: Lfm2Config, view, index: int):
    """One row a lane of a decode batch h (Sb, D): the sum over each
    owned slot's window and the lane's new row, and the window moved one
    row on where it lies, every slot of the layer in one elementwise
    pass; a slot that no lane of this step owns keeps its rows."""
    K = cfg.conv_L_cache
    g, C = _conv_inputs(h, p, cfg)
    state = view.all(index)
    with jax.named_scope("conv.window"):
        rows = [state[f"conv{j}"] for j in range(K - 1)]  # (slots, D) each
        rows.append(view.to_slots(g).astype(rows[0].dtype))
        w = p["conv_w"].astype(jnp.float32)
        c = view.from_slots(
            sum(w[j] * rows[j].astype(jnp.float32) for j in range(K)))
        for j in range(K - 1):
            view.set_all(index, f"conv{j}", jnp.where(
                view.owned[:, None], rows[j + 1], rows[j]))
    return _conv_output(c, C, p, cfg)


def _rope(x, positions, theta: float):
    """Every head of x (..., heads, D) rotated by `positions` (the
    leading dimensions'), half-split pairs ``(i, i + D / 2)``."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    angles = positions.astype(jnp.float32)[..., None, None] * freqs
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([(x1 * cos - x2 * sin).astype(x.dtype),
                            (x1 * sin + x2 * cos).astype(x.dtype)], axis=-1)


def _qkv(h, p, positions, cfg: Lfm2Config):
    """Normed rows h (..., D) at `positions` -> q (..., HK, R, hd) and k
    (..., HK, hd), both normalised a head and then rotated, and v (...,
    HK, hd): the R query heads of a KV head side by side."""
    dt = cfg.dtype
    H, HK, hd = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    lead = h.shape[:-1]
    q = (h @ p["wq"].astype(dt)).reshape(*lead, H, hd)
    k = (h @ p["wk"].astype(dt)).reshape(*lead, HK, hd)
    v = (h @ p["wv"].astype(dt)).reshape(*lead, HK, hd)
    q = _rope(_rmsnorm(q, p["q_norm"], cfg.norm_eps), positions,
              cfg.rope_theta)
    k = _rope(_rmsnorm(k, p["k_norm"], cfg.norm_eps), positions,
              cfg.rope_theta)
    return q.reshape(*lead, HK, H // HK, hd), k, v


def _project(att, p, cfg: Lfm2Config):
    """att (B, T, HK, R, hd) -> (B, T, D) after `wo`."""
    B, T = att.shape[:2]
    return att.reshape(B, T, -1) @ p["wo"].astype(cfg.dtype)


def _dense(h, p, cfg: Lfm2Config):
    dt = cfg.dtype
    with jax.named_scope("ffn.dense"):
        return (jax.nn.silu(h @ p["w1"].astype(dt))
                * (h @ p["w3"].astype(dt))) @ p["w2"].astype(dt)


def _experts(h, p, cfg: Lfm2Config):
    """Normed rows h (N, D) -> (the held experts' part of the routed sum,
    pairs per expert over ALL experts)."""
    dt = cfg.dtype
    wg, wu, wd = (p[n].astype(dt) for n in ("we_gate", "we_up", "we_down"))
    y, counts, _ = routed_experts(
        h, p["router"],
        lambda a, mm: mm(jax.nn.silu(mm(a, wg)) * mm(a, wu), wd),
        k=cfg.num_experts_per_tok, norm_topk=cfg.norm_topk_prob,
        score="sigmoid",
        select_bias=p["expert_bias"] if cfg.use_expert_bias else None,
        scale=cfg.routed_scaling_factor,
        held=(cfg.expert_offset, cfg.experts_held), norm_eps=1e-6)
    return y, counts


def _stack(params, x, cfg: Lfm2Config, conv, attention):
    """The blocks in `layer_types`' order on x (B, T, D) or (B, D).
    ``conv(h, p, i)`` and ``attention(h, p, i) -> (out, k, v)`` are the
    program's way through those two operators, `i` counting the layers of
    that kind; the feed-forwards are the same in every program. Returns
    (logits f32, k, v stacked over the attention layers, pairs per expert
    stacked over the expert layers)."""
    eps = cfg.norm_eps
    seen = {CONV: 0, ATTENTION: 0}
    ks, vs, counts = [], [], []
    for n, (kind, p) in enumerate(zip(cfg.layer_types, params["layers"])):
        h = _rmsnorm(x, p["operator_norm"], eps)
        if kind == CONV:
            y = conv(h, p, seen[kind])
        else:
            y, k, v = attention(h, p, seen[kind])
            ks.append(k)
            vs.append(v)
        seen[kind] += 1
        x = x + y
        h = _rmsnorm(x, p["ffn_norm"], eps)
        if n >= cfg.num_dense_layers:
            y, c = _experts(h.reshape(-1, h.shape[-1]), p, cfg)
            y = y.reshape(h.shape)
            counts.append(c)
        else:
            y = _dense(h, p, cfg)
        x = x + y
    x = _rmsnorm(x, params["embedding_norm"], eps)
    # the head is the embedding (tied)
    logits = jnp.einsum("...d,vd->...v", x, params["wte"].astype(cfg.dtype),
                        preferred_element_type=jnp.float32)
    return logits, jnp.stack(ks), jnp.stack(vs), jnp.stack(counts)


# --------------------------------------------------------------------------
# KV-cache and state inference steps (serve.llm): the models own the
# mathematics, serve/llm/runner.py the pages, `state` (a cache.StateView)
# the conv windows' reads and writes.


def lfm2_prefill_kv(params: Params, tokens: jax.Array, cfg: Lfm2Config, *,
                    state, n_valid):
    """A whole prompt from position 0: tokens (1, T), of which the first
    `n_valid` are real -> (logits (1, T, Vp) f32, k, v (n_kv_layers, 1,
    T, HK, hd), pairs (n_expert_layers, num_experts))."""
    B, T = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(T), (B, T))
    valid = jnp.tril(jnp.ones((T, T), bool))[None]

    def conv(h, p, i):
        return _conv_rows(h[0], p, cfg, state, i, n_valid)[None]

    def attention(h, p, i):
        q, k, v = _qkv(h, p, positions, cfg)
        att = softmax_over(q, [(k, v, valid)], 1.0 / math.sqrt(cfg.head_dim),
                           cfg.dtype)
        return _project(att, p, cfg), k, v

    x = params["wte"].astype(cfg.dtype)[tokens]
    return _stack(params, x, cfg, conv, attention)


def lfm2_prefill_chunk_kv(params: Params, tokens: jax.Array, start, ctx,
                          chunk_mask, cfg: Lfm2Config, *, state, n_valid):
    """A chunk at positions start..start+T-1: ``ctx`` holds the attention
    layers' cached context (rows (HK, hd), K rotated) for positions <
    start, the conv layers start from the window the lane's last chunk
    left."""
    B, T = tokens.shape
    positions = start + jnp.broadcast_to(jnp.arange(T), (B, T))
    own = causal_rows(chunk_mask)

    def conv(h, p, i):
        return _conv_rows(h[0], p, cfg, state, i, n_valid)[None]

    def attention(h, p, i):
        q, k, v = _qkv(h, p, positions, cfg)
        return _project(attend_cached(q, k, v, own, ctx, i, cfg.dtype), p,
                        cfg), k, v

    x = params["wte"].astype(cfg.dtype)[tokens]
    return _stack(params, x, cfg, conv, attention)


def lfm2_decode_kv(params: Params, tokens: jax.Array, positions, ctx,
                   cfg: Lfm2Config, *, state):
    """One token a lane: tokens (B,) at `positions`, against the lanes'
    cached context ``ctx`` -> (logits (B, Vp) f32, k_new, v_new
    (n_kv_layers, B, HK, hd), pairs)."""
    B = tokens.shape[0]
    own = jnp.ones((B, 1, 1), bool)

    def conv(h, p, i):
        return _conv_step(h, p, cfg, state, i)

    def attention(h, p, i):
        q, k, v = _qkv(h, p, positions, cfg)
        att = attend_cached(q[:, None], k[:, None], v[:, None], own, ctx, i,
                            cfg.dtype)
        return _project(att, p, cfg)[:, 0], k, v

    x = params["wte"].astype(cfg.dtype)[tokens]
    return _stack(params, x, cfg, conv, attention)
