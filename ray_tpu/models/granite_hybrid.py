"""granite_hybrid: a stateful mixer AND a routed layer in every layer.

Seventh served family, after IBM's Granite 4.0-H hybrids (`model_type`
granitemoehybrid). Every layer is two half-layers, each under its own
RMSNorm, each added to the stream times `residual_multiplier` r:

    x = x + r * mixer(rmsnorm(x));  x = x + r * (routed(h) + shared(h)),
    h = rmsnorm(x)

with `layer_types` saying which mixer a layer has:

- ``mamba``, a Mamba-2 mixer (models/mamba2.py, shared with nemotron_h)
  at ONE group: all `mamba_n_heads` heads read the same B and C, the
  convolution has a bias, and the gated norm is over all of `d_inner`;
- ``attention``: causal softmax attention with grouped K and V heads, no
  bias and NO rotation of q and k (`position_embedding_type` nope: the
  Mamba layers carry order), the scores scaled by `attention_multiplier`
  (1 / head_dim, not its root);
- the routed half, in EVERY layer (models/moe.py): router logits over all
  `num_local_experts`, the `num_experts_per_tok` largest LOGITS chosen,
  their weights the softmax over the chosen logits alone (the softmax
  over all experts renormalised over the chosen: the same numbers); no
  bias, no scaling; SwiGLU experts ``W_out(silu(a) * b)``, ``a | b =
  W_in x`` (gate and up held as two matrices); and a shared SwiGLU MLP of
  `shared_intermediate_size` that every row goes through. `experts_held`
  and `expert_offset` say which of the router's experts this chip holds:
  it routes over all of them and computes its own experts' part of the
  result.

The embedding's rows are multiplied by `embedding_multiplier`; after the
last layer one RMSNorm, then the head, which is the embedding (tied), and
the logits are divided by `logits_scaling`.

`layer_types` is a Python loop over the layers, each kind written once,
and the parameters are one dict a layer.

Two kinds of cached state (serve/llm/cache.py): the attention layers' K
and V in pages (`n_kv_layers` of them, one layer in ten), and a Mamba
layer's recurrent state a lane slot (`Mamba2Sizes.state_parts`: three
conv rows in `dtype`, the SSM state in float32).

Matrix products are in `dtype` (bf16: float32 accumulation on the MXU);
the state, the decay, the norms, the softmax and the router are float32.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any

import jax
import jax.numpy as jnp

from ray_tpu.models import mamba2
from ray_tpu.models.moe import routed_experts
from ray_tpu.ops.context_attention import (
    attend_cached,
    causal_rows,
    softmax_over,
)
from ray_tpu.parallel.sharding import PartitionRules

Params = Any

MAMBA, ATTENTION = "mamba", "attention"
# granite-4.0-h-small's `layer_types`: attention at 5, 15, 25, 35 of 40
_LAYERS_H_SMALL = tuple(
    ATTENTION if i % 10 == 5 else MAMBA for i in range(40))


@dataclasses.dataclass(frozen=True)
class GraniteHybridConfig:
    """Field names are the published config.json's, but for `head_dim`
    (the config has no key: hidden_size / num_attention_heads), the two
    that say what is held here, and the three that say how seeded weights
    are drawn."""

    vocab_size: int = 100352
    hidden_size: int = 4096
    layer_types: tuple = _LAYERS_H_SMALL
    # mamba
    mamba_n_heads: int = 128
    mamba_d_head: int = 64
    mamba_d_state: int = 128
    mamba_n_groups: int = 1
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_chunk_size: int = 256
    mamba_conv_bias: bool = True
    # attention
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    head_dim: int = 128
    # routed and shared
    num_local_experts: int = 72
    num_experts_per_tok: int = 10
    intermediate_size: int = 768  # one expert's width
    shared_intermediate_size: int = 1536
    experts_held: int = 72  # of num_local_experts, from expert_offset on
    expert_offset: int = 0
    # the four multipliers
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    attention_multiplier: float = 0.0078125
    logits_scaling: float = 16.0
    rms_norm_eps: float = 1e-5
    max_position_embeddings: int = 131072
    # seeded weights (`init_granite_hybrid`)
    initializer_range: float = 0.02  # std of a seeded matrix
    embedding_range: float | None = None  # of the embedding; None: the same
    final_norm_init: float = 1.0  # the last norm's scale
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16  # what `init_granite_hybrid` creates

    def __post_init__(self):
        if set(self.layer_types) - {MAMBA, ATTENTION}:
            raise ValueError(f"layer_types {self.layer_types!r}: only "
                             f"{MAMBA!r} and {ATTENTION!r} are layer kinds")
        if self.expert_offset + self.experts_held > self.num_local_experts:
            raise ValueError("experts held lie outside the router's range")
        if self.mamba_n_heads * self.mamba_d_head \
                != self.mamba_expand * self.hidden_size:
            raise ValueError("mamba_n_heads x mamba_d_head is not "
                             "mamba_expand x hidden_size")

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
    def n_ssm_layers(self) -> int:
        return self.layer_types.count(MAMBA)

    @property
    def mamba(self) -> mamba2.Mamba2Sizes:
        """What the shared mixer (models/mamba2.py) asks of a family."""
        return mamba2.Mamba2Sizes(
            heads=self.mamba_n_heads, head_dim=self.mamba_d_head,
            state=self.mamba_d_state, groups=self.mamba_n_groups,
            conv_kernel=self.mamba_d_conv, chunk=self.mamba_chunk_size,
            eps=self.rms_norm_eps, dtype=self.dtype)

    def state_parts(self) -> tuple:
        """(name, shape a lane and layer, dtype) of a Mamba layer's
        recurrent state, for `cache.StateLayout`."""
        return self.mamba.state_parts()

    @staticmethod
    def tiny() -> "GraniteHybridConfig":
        """Both mixers at a size for CPU tests, float32: 12 experts of
        which 6 (from the 3rd on) are held, 3 a token, chunks of 8 rows,
        one group."""
        return GraniteHybridConfig(
            vocab_size=512, hidden_size=64,
            layer_types=(MAMBA, MAMBA, ATTENTION, MAMBA),
            mamba_n_heads=8, mamba_d_head=16, mamba_d_state=16,
            mamba_chunk_size=8, num_attention_heads=4,
            num_key_value_heads=2, head_dim=16, num_local_experts=12,
            num_experts_per_tok=3, intermediate_size=32,
            shared_intermediate_size=48, experts_held=6, expert_offset=3,
            attention_multiplier=1.0 / 16, max_position_embeddings=256,
            initializer_range=0.1, dtype=jnp.float32,
            param_dtype=jnp.float32)

    @staticmethod
    def h_small() -> "GraniteHybridConfig":
        """granite-4.0-h-small as published
        (huggingface.co/ibm-granite/granite-4.0-h-small, config.json): 40
        layers of 4096, every expert held (64 GB in bf16: the base of the
        cut below, served nowhere here)."""
        return GraniteHybridConfig()

    @staticmethod
    def h_small_l10_ep4() -> "GraniteHybridConfig":
        """One chip's share where the four chips of a v5e host share each
        layer: layers 0-9 of 40 (one whole period: 5 mamba, attention, 4
        mamba), 18 of the 72 experts and 25,088 of the 100,352 vocabulary
        rows; every width as published (PERF.md section 4)."""
        full = GraniteHybridConfig.h_small()
        return dataclasses.replace(
            full, layer_types=full.layer_types[:10], experts_held=18,
            vocab_size=25088, max_position_embeddings=1792)


def granite_hybrid_partition_rules() -> PartitionRules:
    """The held experts over `expert`; the vocabulary over `tensor`;
    everything else (mixers, router, shared MLP) whole on every device,
    as the stated deployment has it."""
    from jax.sharding import PartitionSpec as P

    return PartitionRules([
        (r"layers/\d+/(we_gate|we_up|we_down)$", P("expert", None, None)),
        (r"wte$", P("tensor", None)),
        (r".*", P()),
    ])


@functools.partial(jax.jit, static_argnames=("cfg",))
def init_granite_hybrid(key: jax.Array, cfg: GraniteHybridConfig) -> Params:
    """One program for the whole tree, every leaf drawn in float32 and
    written in `cfg.param_dtype` by the same fusion. Matrices are normal
    with std `initializer_range`, none rescaled by depth (the family's
    `residual_multiplier` is what damps a half-layer's write), the
    embedding with std `embedding_range`; norm scales 1 but the last
    norm's (`final_norm_init`). The state-space parameters follow
    nemotron_h's rule: `A_log` the log of uniform 1..16, `dt_bias` the
    inverse softplus of a dt log-uniform in 0.001..0.1, `D` 1, the conv
    and its bias as torch's Conv1d default (uniform within 1 /
    sqrt(mamba_d_conv))."""
    L, D, V = cfg.n_layer, cfg.hidden_size, cfg.padded_vocab
    pdt = cfg.param_dtype
    std = cfg.initializer_range
    emb_std = std if cfg.embedding_range is None else cfg.embedding_range
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

    def mamba(k):
        ks = jax.random.split(k, 6)
        s = cfg.mamba
        H, C, K = s.heads, s.conv_dim, s.conv_kernel
        bound = 1.0 / math.sqrt(K)
        dt = jnp.exp(jax.random.uniform(ks[2], (H,), jnp.float32)
                     * (math.log(0.1) - math.log(0.001)) + math.log(0.001))
        p = {
            "in_proj": normal(ks[0], (D, s.d_inner + C + H), std),
            "conv_w": jax.random.uniform(
                ks[1], (K, C), jnp.float32, -bound, bound).astype(pdt),
            "dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(pdt),
            "A_log": jnp.log(jax.random.uniform(
                ks[3], (H,), jnp.float32, 1.0, 16.0)).astype(pdt),
            "D": jnp.ones((H,), pdt),
            "gate_norm": jnp.ones((s.d_inner,), pdt),
            "out_proj": normal(ks[4], (s.d_inner, D), std),
        }
        if cfg.mamba_conv_bias:
            p["conv_b"] = jax.random.uniform(
                ks[5], (C,), jnp.float32, -bound, bound).astype(pdt)
        return p

    def attention(k):
        ks = jax.random.split(k, 4)
        q_dim = cfg.num_attention_heads * cfg.head_dim
        kv_dim = cfg.num_key_value_heads * cfg.head_dim
        return {
            "wq": normal(ks[0], (D, q_dim), std),
            "wk": normal(ks[1], (D, kv_dim), std),
            "wv": normal(ks[2], (D, kv_dim), std),
            "wo": normal(ks[3], (q_dim, D), std),
        }

    def feed_forward(k):
        ks = jax.random.split(k, 7)
        X, F = cfg.experts_held, cfg.intermediate_size
        Fs = cfg.shared_intermediate_size
        return {
            "router": normal(ks[0], (D, cfg.num_local_experts), std),
            "we_gate": experts(ks[1], (X, D, F), std),
            "we_up": experts(ks[2], (X, D, F), std),
            "we_down": experts(ks[3], (X, F, D), std),
            "ws_gate": normal(ks[4], (D, Fs), std),
            "ws_up": normal(ks[5], (D, Fs), std),
            "ws_down": normal(ks[6], (Fs, D), std),
        }

    make = {MAMBA: mamba, ATTENTION: attention}
    layers = []
    for kind, k in zip(cfg.layer_types, jax.random.split(k_layers, L)):
        km, kf = jax.random.split(k)
        layers.append({"input_norm": jnp.ones((D,), pdt), **make[kind](km),
                       "post_norm": jnp.ones((D,), pdt), **feed_forward(kf)})
    return {"wte": normal(k_wte, (V, D), emb_std), "layers": layers,
            "norm": jnp.full((D,), cfg.final_norm_init, pdt)}


# --------------------------------------------------------------------------
# the attention mixer and the routed half, each written once (the Mamba-2
# mixer is models/mamba2.py's)


def _rmsnorm(x, scale, eps):
    x32 = x.astype(jnp.float32)
    rms = jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (x32 * rms * scale.astype(jnp.float32)).astype(x.dtype)


def _qkv(h, p, cfg: GraniteHybridConfig):
    """Normed rows h (..., D) -> q (..., HK, R, hd), k and v (..., HK,
    hd): the R query heads of a group side by side, not rotated. The
    softmax of ops/context_attention.py scales by ``1 / sqrt(hd)``, so q
    carries the rest of `attention_multiplier` (``multiplier * sqrt(hd)``,
    applied to the product's float32 result before its one rounding)."""
    dt = cfg.dtype
    HK, hd = cfg.num_key_value_heads, cfg.head_dim
    R = cfg.num_attention_heads // HK
    lead = h.shape[:-1]
    q = jnp.matmul(h, p["wq"].astype(dt), preferred_element_type=jnp.float32)
    q = (q * (cfg.attention_multiplier * math.sqrt(hd))).astype(dt)
    return (q.reshape(*lead, HK, R, hd),
            (h @ p["wk"].astype(dt)).reshape(*lead, HK, hd),
            (h @ p["wv"].astype(dt)).reshape(*lead, HK, hd))


def _project(att, p, cfg: GraniteHybridConfig):
    """att (B, T, HK, R, hd) -> (B, T, D) after `wo`."""
    B, T = att.shape[:2]
    return att.reshape(B, T, -1) @ p["wo"].astype(cfg.dtype)


def _experts(h, p, cfg: GraniteHybridConfig):
    """Normed rows h (N, D) -> (the held experts' part of the routed sum
    plus the shared MLP, pairs per expert over ALL experts)."""
    dt = cfg.dtype
    wg, wu, wd = (p[n].astype(dt) for n in ("we_gate", "we_up", "we_down"))

    def shared(rows):
        a = jax.nn.silu(rows @ p["ws_gate"].astype(dt)) \
            * (rows @ p["ws_up"].astype(dt))
        return a @ p["ws_down"].astype(dt)

    y, counts, _ = routed_experts(
        h, p["router"],
        lambda a, mm: mm(jax.nn.silu(mm(a, wg)) * mm(a, wu), wd),
        k=cfg.num_experts_per_tok, norm_topk=True, score="softmax",
        held=(cfg.expert_offset, cfg.experts_held), shared=shared)
    return y, counts


def _stack(params, tokens, cfg: GraniteHybridConfig, mamba, attention):
    """The layers in `layer_types`' order on the embedded tokens (B, T)
    or (B,). ``mamba(h, p, i)`` and ``attention(h, p, i) -> (out, k, v)``
    are the program's way through the two mixers, `i` counting the layers
    of that kind; the routed half is the same in every program. Returns
    (logits f32, k, v stacked over the attention layers, pairs per expert
    stacked over the layers)."""
    eps, r = cfg.rms_norm_eps, cfg.residual_multiplier
    wte = params["wte"].astype(cfg.dtype)
    f32 = jnp.float32
    x = (wte[tokens].astype(f32) * cfg.embedding_multiplier).astype(cfg.dtype)

    def add(x, y):  # the residual add in float32, rounded once
        return (x.astype(f32) + r * y.astype(f32)).astype(x.dtype)

    seen = {MAMBA: 0, ATTENTION: 0}
    ks, vs, counts = [], [], []
    for kind, p in zip(cfg.layer_types, params["layers"]):
        h = _rmsnorm(x, p["input_norm"], eps)
        if kind == MAMBA:
            y = mamba(h, p, seen[kind])
        else:
            y, k, v = attention(h, p, seen[kind])
            ks.append(k)
            vs.append(v)
        seen[kind] += 1
        x = add(x, y)
        h = _rmsnorm(x, p["post_norm"], eps)
        y, c = _experts(h.reshape(-1, h.shape[-1]), p, cfg)
        counts.append(c)
        x = add(x, y.reshape(h.shape))
    x = _rmsnorm(x, params["norm"], eps)
    # the head is the embedding (tied)
    logits = jnp.einsum("...d,vd->...v", x, wte,
                        preferred_element_type=jnp.float32)
    return (logits / cfg.logits_scaling, jnp.stack(ks), jnp.stack(vs),
            jnp.stack(counts))


# --------------------------------------------------------------------------
# KV-cache and state inference steps (serve.llm): the models own the
# mathematics, serve/llm/runner.py the pages, `state` (a cache.StateView)
# the recurrent state's reads and writes.


def granite_hybrid_prefill_kv(params: Params, tokens: jax.Array,
                              cfg: GraniteHybridConfig, *, state, n_valid):
    """A whole prompt from position 0: tokens (1, T), of which the first
    `n_valid` are real -> (logits (1, T, Vp) f32, k, v (n_kv_layers, 1,
    T, HK, hd), pairs (n_layer, num_local_experts))."""
    T = tokens.shape[1]
    valid = jnp.tril(jnp.ones((T, T), bool))[None]

    def mamba(h, p, i):
        return mamba2.rows(h[0], p, cfg.mamba, state, i, n_valid)[None]

    def attention(h, p, i):
        q, k, v = _qkv(h, p, cfg)
        att = softmax_over(q, [(k, v, valid)], 1.0 / math.sqrt(cfg.head_dim),
                           cfg.dtype)
        return _project(att, p, cfg), k, v

    return _stack(params, tokens, cfg, mamba, attention)


def granite_hybrid_prefill_chunk_kv(params: Params, tokens: jax.Array, start,
                                    ctx, chunk_mask,
                                    cfg: GraniteHybridConfig, *, state,
                                    n_valid):
    """A chunk at positions start..start+T-1: ``ctx`` holds the attention
    layers' cached context (rows (HK, hd)) for positions < start, the
    Mamba layers start from the state the lane's last chunk left."""
    own = causal_rows(chunk_mask)

    def mamba(h, p, i):
        return mamba2.rows(h[0], p, cfg.mamba, state, i, n_valid)[None]

    def attention(h, p, i):
        q, k, v = _qkv(h, p, cfg)
        return _project(attend_cached(q, k, v, own, ctx, i, cfg.dtype), p,
                        cfg), k, v

    return _stack(params, tokens, cfg, mamba, attention)


def granite_hybrid_decode_kv(params: Params, tokens: jax.Array, positions,
                             ctx, cfg: GraniteHybridConfig, *, state):
    """One token a lane: tokens (B,), against the lanes' cached context
    ``ctx`` -> (logits (B, Vp) f32, k_new, v_new (n_kv_layers, B, HK,
    hd), pairs)."""
    B = tokens.shape[0]
    own = jnp.ones((B, 1, 1), bool)

    def mamba(h, p, i):
        return mamba2.step(h, p, cfg.mamba, state, i)

    def attention(h, p, i):
        q, k, v = _qkv(h, p, cfg)
        att = attend_cached(q[:, None], k[:, None], v[:, None], own, ctx, i,
                            cfg.dtype)
        return _project(att, p, cfg)[:, 0], k, v

    return _stack(params, tokens, cfg, mamba, attention)
