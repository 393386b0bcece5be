"""xing4: latent attention (MLA) that reads every cached slot, a few
leading dense feed-forwards, then routed experts beside a shared one, on a
residual path that is `hc_mult` streams wide and mixed every half-layer by
a doubly stochastic matrix (Manifold-Constrained Hyper-Connections).

Eighth model family, after XingChen-AGI's Xing4.0-29B-A4B (`model_type`
xing4_0). Attention, rotation and feed-forwards are DeepSeek-V3's and are
written in models/mla.py, which the glm_dsa family runs too; the residual
path is this module's. With X a token's residual state, n = `hc_mult` rows
of C = `hidden_size`:

- **entry**: ``X_0[i] = wte[token]`` for every stream i; **exit**: ``x =
  sum_i X_L[i]``, then the final RMSNorm and the head;
- **a half-layer** (attention or feed-forward, each with maps of its own:
  `phi` (nC, n^2 + 2n), `alpha` = (pre, post, res), `b_pre` (n), `b_post`
  (n), `b_res` (n, n)), in float32:
  - ``x = vec(X)``; ``u = (x * rsqrt(mean(x^2) + rms_norm_eps)) @ phi``
  - ``H_pre = sigmoid(alpha_pre u[0:n] + b_pre)``;
    ``H_post = 2 sigmoid(alpha_post u[n:2n] + b_post)``
  - ``M = exp(clip(alpha_res mat(u[2n:]) + b_res, mhc_h_res_clamp_min,
    mhc_h_res_clamp_max))``; `hc_sinkhorn_iters` times: ``M = M /
    (colsum(M) + hc_eps)``, then ``M = M / (rowsum(M) + hc_eps)``; ``H_res
    = M`` (every iteration is run: 20 as published)
  - ``h = sum_i H_pre[i] X[i]``; ``y = F(rmsnorm(h))``, F the layer's
    attention or its feed-forward under the layer's own norm scale
  - ``X'[i] = sum_j H_res[i, j] X[j] + H_post[i] y``
- **attention**: MLA as models/mla.py has it, no indexer: a row attends
  every earlier slot. Scores times ``mscale^2 / sqrt(qk_nope_head_dim +
  qk_rope_head_dim)``, ``mscale = 0.1 mscale_all_dim ln(factor) + 1``; the
  rotated lanes turn at YaRN's blended frequencies (`mla.yarn_frequencies`;
  the attention factor on cos and sin is ``mscale / mscale_all_dim``'s, 1
  as published), interleaved pairs. A whole prompt's own rows are
  up-projected (`_attend_rows`); a chunk's and a decode step's rows attend
  ABSORBED to the cached latent rows and their own
  (ops/context_attention.py `attend_latent`): one 640-lane row a slot
  serves all heads, so a decode step reads 1,280 B a slot and layer where
  up-projected keys and values would be 20 KB (on the chip with the paged
  Pallas kernel, ops/paged_attention.py, the row one KV head under 32
  query heads: PERF.md section 6, PR 52), and a chunk's 256 rows, which
  keep the tile loops, pay 3.4 times the per-head products for not
  writing and reading those 20 KB a slot again (PERF.md section 6, PR 51,
  has the arithmetic);
- **feed-forward**: layers below `first_k_dense_replace` a dense SwiGLU,
  the others routed experts (sigmoid scores, a selection bias, weights
  normalised and times `routed_scaling_factor`, the shared expert on
  every row); `experts_held` and `expert_offset` say which of the router's
  experts this chip holds.

One kind of KV layer (serve/llm/cache.py `KVKind` with a `v_head_dim` of
0: a latent kind with no indexer): the forwards return the latent rows
where another family returns k and rows of no lanes where it returns v.
The multi-token-prediction layer (`num_nextn_predict_layers`) is not
served. The coefficients and the two mixes are computed in float32 (the
14,336 x 24 product at the highest precision); X is held in `dtype`, the
streams side by side in one row of n C lanes. Matrix products are in
`dtype`; norms, rotation, softmax and router are float32.

Nothing is built when this module is imported (`runner.adapters()`
imports every family's).
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any

import jax
import jax.numpy as jnp

from ray_tpu.models import mla
from ray_tpu.ops import mhc_maps
from ray_tpu.ops.context_attention import causal_rows
from ray_tpu.parallel.sharding import PartitionRules

Params = Any


@dataclasses.dataclass(frozen=True)
class Xing4Config:
    """Field names are the published config.json's (`rope_scaling`'s keys
    under `rope_`), but for those that say what is held here and the
    seeded weights' spread."""

    vocab_size: int = 131072
    hidden_size: int = 3584
    num_hidden_layers: int = 40
    first_k_dense_replace: int = 2
    # attention
    num_attention_heads: int = 32
    q_lora_rank: int = 768
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 1e4
    rope_factor: float = 64.0
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 1.0
    original_max_position_embeddings: int = 4096
    # lanes of zeros behind ``[c_kv | k_pe]`` in the cached row, as
    # glm_dsa's and for its reason (tests/test_kv_pool_layout.py)
    latent_pad: int = 64
    # the residual streams
    hc_mult: int = 4
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    mhc_h_res_clamp_min: float = -30.0
    mhc_h_res_clamp_max: float = 30.0
    # feed-forward
    intermediate_size: int = 9216
    moe_intermediate_size: int = 1024
    n_routed_experts: int = 64
    n_shared_experts: int = 1
    num_experts_per_tok: int = 4
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.0
    experts_held: int = 64  # of n_routed_experts, from expert_offset on
    expert_offset: int = 0
    rms_norm_eps: float = 1e-6
    initializer_range: float = 0.02  # std of a seeded matrix
    # std of a seeded map's dynamic logits ``u`` (phi is normal with this
    # over sqrt(n C), x being normed) and of `b_res`: together the logits
    # of H_res have std 1 and spread over some five units, so that H_res
    # is far from the identity and from the uniform matrix (its largest
    # entry 0.56 in the median) while 20 Sinkhorn iterations still bring
    # every row and column within 3e-4 of 1 (at std 1.5 each they do not:
    # 1e-2; tests/test_xing4.py)
    hc_logit_std: float = 0.7
    max_position_embeddings: int = 262144
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16  # what `init_xing4` creates

    def __post_init__(self):
        if self.expert_offset + self.experts_held > self.n_routed_experts:
            raise ValueError("experts held lie outside the router's range")
        if self.n_shared_experts != 1:
            raise ValueError("one shared expert a layer is what is written")
        if self.first_k_dense_replace > self.num_hidden_layers:
            raise ValueError("more leading dense layers than layers")

    # what the engine asks of every family's config
    @property
    def n_layer(self) -> int:
        return self.num_hidden_layers

    @property
    def block_size(self) -> int:
        return self.max_position_embeddings

    @property
    def padded_vocab(self) -> int:
        return ((self.vocab_size + 127) // 128) * 128

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def softmax_scale(self) -> float:
        """``mscale^2 / sqrt(qk_head_dim)``."""
        return mla.yarn_mscale(self.rope_factor, self.rope_mscale_all_dim) \
            ** 2 / math.sqrt(self.qk_head_dim)

    def rotate(self, x, positions):
        with jax.named_scope("attn.rope.yarn"):
            return mla.rope(x, positions, mla.yarn_frequencies(
                self.rope_theta, self.qk_rope_head_dim,
                factor=self.rope_factor,
                original=self.original_max_position_embeddings,
                beta_fast=self.rope_beta_fast,
                beta_slow=self.rope_beta_slow), self.qk_rope_head_dim)

    @property
    def latent_row(self) -> int:
        """Lanes of the row cached a token and layer: ``[c_kv | k_pe |
        zeros]``."""
        return self.kv_lora_rank + self.qk_rope_head_dim + self.latent_pad

    @property
    def hc_maps(self) -> int:
        """Columns of a half-layer's `phi`: H_pre, H_post, H_res."""
        return self.hc_mult * (self.hc_mult + 2)

    def kv_kinds(self) -> tuple[tuple, ...]:
        """The one kind of KV layer, the fields of a serve/llm/cache.py
        `KVKind`: one head whose K row is the latent row, no second row
        (a latent kind with no indexer), no window, nothing chosen."""
        return (("latent", self.n_layer, 1, self.latent_row, 0, None, None),)

    def n_params(self) -> int:
        """Parameters of the tree at `vocab_size` rows (the padding rows
        of the embedding and the head not counted)."""
        D, H, n = self.hidden_size, self.num_attention_heads, self.hc_mult
        attn = (D * self.q_lora_rank + self.q_lora_rank * H * self.qk_head_dim
                + D * (self.kv_lora_rank + self.qk_rope_head_dim)
                + self.kv_lora_rank * H * (self.qk_nope_head_dim
                                           + self.v_head_dim)
                + H * self.v_head_dim * D
                + D + self.q_lora_rank + self.kv_lora_rank)
        maps = n * D * self.hc_maps + 3 + 2 * n + n * n  # a half-layer's
        dense = 3 * D * self.intermediate_size + D
        expert = 3 * D * self.moe_intermediate_size
        routed = (D * self.n_routed_experts + self.n_routed_experts
                  + (self.experts_held + 1) * expert + D)
        n_dense = self.first_k_dense_replace
        return (self.n_layer * (attn + 2 * maps) + n_dense * dense
                + (self.n_layer - n_dense) * routed
                + 2 * self.vocab_size * D + D)

    @staticmethod
    def tiny() -> "Xing4Config":
        """Every mechanism at a size for CPU tests, float32: 4 streams of
        64, YaRN over an original context of 16, 16 experts of which 4
        (from the 4th on) are held."""
        return Xing4Config(
            vocab_size=512, hidden_size=64, num_hidden_layers=3,
            first_k_dense_replace=1, num_attention_heads=4, q_lora_rank=32,
            kv_lora_rank=24, qk_nope_head_dim=12, qk_rope_head_dim=8,
            v_head_dim=16, rope_factor=8.0,
            original_max_position_embeddings=16, latent_pad=8,
            intermediate_size=96, moe_intermediate_size=32,
            n_routed_experts=16, num_experts_per_tok=3, experts_held=4,
            expert_offset=4,
            max_position_embeddings=256, dtype=jnp.float32,
            param_dtype=jnp.float32)

    @staticmethod
    def xing4_29b_a4b() -> "Xing4Config":
        """Xing4.0-29B-A4B as published (huggingface.co/XingChen-AGI/
        Xing4.0-29B-A4B, config.json): 40 blocks of 3584, every expert
        held (59 GB in bf16: the base of the cut below, served nowhere
        here)."""
        return Xing4Config()

    @staticmethod
    def xing4_29b_a4b_l6_ep4() -> "Xing4Config":
        """One chip's share where four chips share each layer: layer 0
        (dense; the two leading dense layers count once) and expert layers
        2-6, 16 of the 64 experts and 32,768 of the 131,072 vocabulary
        rows; every width as published (PERF.md section 4)."""
        return dataclasses.replace(
            Xing4Config.xing4_29b_a4b(), num_hidden_layers=6,
            first_k_dense_replace=1, experts_held=16, vocab_size=32768,
            max_position_embeddings=33280)


def xing4_partition_rules() -> PartitionRules:
    """The held experts over `expert`; the vocabulary over `tensor`;
    attention, the streams' maps, router, shared expert and the dense
    feed-forward whole on every device, as the stated deployment has it
    (a latent row cannot be split by head)."""
    from jax.sharding import PartitionSpec as P

    return PartitionRules([
        (r"layers/\d+/(we_gate|we_up|we_down)$", P("expert", None, None)),
        (r"wte$", P("tensor", None)),
        (r"lm_head$", P(None, "tensor")),
        (r".*", P()),
    ])


@functools.partial(jax.jit, static_argnames=("cfg",))
def init_xing4(key: jax.Array, cfg: Xing4Config) -> Params:
    """One program for the whole tree, every leaf drawn in float32 and
    written in `cfg.param_dtype` by the same fusion, but for the streams'
    maps, which are float32 whatever the dtype (the coefficients are
    computed in float32). Matrices are normal with std
    `initializer_range`, those that write the residual streams that over
    sqrt(L); norm scales 1. A map's `phi` is normal with std
    ``hc_logit_std / sqrt(n C)`` and `b_res` with std `hc_logit_std`
    (`b_pre`, `b_post` with 1), `alpha` 1: the logits spread over several
    units on normed rows of any width, the dynamic part is as large as the
    static, and H_res is neither the identity nor uniform. `wkv_b` is held
    as its two column groups, `wk_b` (rank, heads, nope) and `wv_b` (rank,
    heads, v), so that neither program slices a weight."""
    L, D, V = cfg.n_layer, cfg.hidden_size, cfg.padded_vocab
    H, R, n = cfg.num_attention_heads, cfg.kv_lora_rank, cfg.hc_mult
    pdt = cfg.param_dtype
    std = cfg.initializer_range
    out_std = std / math.sqrt(L)
    k_wte, k_head, k_layers = jax.random.split(key, 3)

    def normal(k, shape, scale, dtype=pdt):
        return (jax.random.normal(k, shape, jnp.float32) * scale) \
            .astype(dtype)

    def maps(k):
        ks = jax.random.split(k, 4)
        f32, s = jnp.float32, cfg.hc_logit_std
        return {"phi": normal(ks[0], (n * D, cfg.hc_maps),
                              s / math.sqrt(n * D), f32),
                "alpha": jnp.ones((3,), f32),
                "b_pre": normal(ks[1], (n,), 1.0, f32),
                "b_post": normal(ks[2], (n,), 1.0, f32),
                "b_res": normal(ks[3], (n, n), s, f32)}

    def attention(k):
        ks = jax.random.split(k, 7)
        return {
            "hc_attn": maps(ks[6]),
            "attn_norm": jnp.ones((D,), pdt),
            "wq_a": normal(ks[0], (D, cfg.q_lora_rank), std),
            "q_norm": jnp.ones((cfg.q_lora_rank,), pdt),
            "wq_b": normal(ks[1], (cfg.q_lora_rank, H * cfg.qk_head_dim),
                           std),
            "wkv_a": normal(ks[2], (D, R + cfg.qk_rope_head_dim), std),
            "kv_norm": jnp.ones((R,), pdt),
            "wk_b": normal(ks[3], (R, H, cfg.qk_nope_head_dim), std),
            "wv_b": normal(ks[4], (R, H, cfg.v_head_dim), std),
            "wo": normal(ks[5], (H * cfg.v_head_dim, D), out_std),
        }

    def feed_forward(k, routed):
        ks = jax.random.split(k, 9)
        if not routed:
            F = cfg.intermediate_size
            return {"hc_ffn": maps(ks[8]),
                    "ffn_norm": jnp.ones((D,), pdt),
                    "w_gate": normal(ks[0], (D, F), std),
                    "w_up": normal(ks[1], (D, F), std),
                    "w_down": normal(ks[2], (F, D), out_std)}
        X, F = cfg.experts_held, cfg.moe_intermediate_size
        return {"hc_ffn": maps(ks[8]),
                "ffn_norm": jnp.ones((D,), pdt),
                "router": normal(ks[3], (D, cfg.n_routed_experts), std),
                "router_bias": normal(ks[4], (cfg.n_routed_experts,), 0.02),
                "we_gate": normal(ks[0], (X, D, F), std),
                "we_up": normal(ks[1], (X, D, F), std),
                "we_down": normal(ks[2], (X, F, D), out_std),
                "ws_gate": normal(ks[5], (D, F), std),
                "ws_up": normal(ks[6], (D, F), std),
                "ws_down": normal(ks[7], (F, D), out_std)}

    layers = []
    for i, k in enumerate(jax.random.split(k_layers, L)):
        ka, kf = jax.random.split(k)
        layers.append({**attention(ka),
                       **feed_forward(kf, i >= cfg.first_k_dense_replace)})
    return {"wte": normal(k_wte, (V, D), std), "layers": layers,
            "lnf": jnp.ones((D,), pdt),
            "lm_head": normal(k_head, (D, V), std)}


# --------------------------------------------------------------------------
# the residual streams: a token's state X is one row of n C lanes, stream i
# the lanes [i C, (i + 1) C); the coefficients have the tokens last


def _streams(X, cfg: Xing4Config):
    C = cfg.hidden_size
    return [X[:, i * C:(i + 1) * C].astype(jnp.float32)
            for i in range(cfg.hc_mult)]


def mhc_coefficients(X, m, cfg: Xing4Config):
    """A half-layer's maps on the states X (N, n C) -> (H_pre (n, N),
    H_post (n, N), H_res (n, n, N)), float32: the normed state's product
    with `phi`, then the maps and all `hc_sinkhorn_iters` Sinkhorn
    iterations, one kernel on the chip (ops/mhc_maps.py)."""
    n = cfg.hc_mult
    with jax.named_scope("resid.mhc.coef"):
        x32 = X.astype(jnp.float32)
        normed = x32 * jax.lax.rsqrt(
            jnp.mean(x32 * x32, axis=-1, keepdims=True) + cfg.rms_norm_eps)
        u = jnp.einsum("nc,ck->kn", normed, m["phi"],
                       precision=jax.lax.Precision.HIGHEST)
        scale = jnp.concatenate([
            jnp.broadcast_to(m["alpha"][i], (k,))
            for i, k in enumerate((n, n, n * n))])[:, None]
        bias = jnp.concatenate([m["b_pre"], m["b_post"],
                                m["b_res"].reshape(-1)])[:, None]
        how = dict(n=n, iters=cfg.hc_sinkhorn_iters, eps=cfg.hc_eps,
                   lo=cfg.mhc_h_res_clamp_min, hi=cfg.mhc_h_res_clamp_max)
        if mhc_maps.runs_as_kernel():
            maps = mhc_maps.mhc_maps(u, scale, bias, **how)
        else:
            maps = mhc_maps.maps_reference(u * scale + bias, **how)
    return maps[:n], maps[n:2 * n], maps[2 * n:].reshape(n, n, -1)


def mhc_pre(X, pre, cfg: Xing4Config):
    """What a half-layer reads: ``h = sum_i H_pre[i] X[i]`` (N, C)."""
    with jax.named_scope("resid.mhc.pre"):
        h = sum(pre[i][:, None] * x for i, x in enumerate(_streams(X, cfg)))
        return h.astype(cfg.dtype)


def mhc_post(X, y, post, res, cfg: Xing4Config):
    """The states after a half-layer: ``X'[i] = sum_j H_res[i, j] X[j] +
    H_post[i] y``."""
    with jax.named_scope("resid.mhc.post"):
        xs, y32 = _streams(X, cfg), y.astype(jnp.float32)
        return jnp.concatenate(
            [post[i][:, None] * y32
             + sum(res[i, j][:, None] * x for j, x in enumerate(xs))
             for i in range(cfg.hc_mult)], axis=-1).astype(cfg.dtype)


# --------------------------------------------------------------------------
# the two ways through the attention


# a whole prompt's rows up-projected, a chunk's and a decode step's
# absorbed: models/mla.py's, under the names benchmark/parity_xing4.py
# calls them by
_attend_rows = mla.attend_rows
_attend_cached = mla.attend_cached


def _stack(params, tokens, cfg: Xing4Config, attention):
    """The blocks on the rows of `tokens` (B, T) or (B,). ``attention(h,
    p, i) -> (out, latent rows)`` is the program's way through layer i's
    attention on rows shaped as `tokens`; the streams and the
    feed-forwards are the same in every program. Returns (logits f32, the
    latent rows stacked over the layers with a head dimension of 1, as the
    pool takes them, rows of no lanes for the pool the kind has not, pairs
    per expert stacked over the expert layers)."""
    eps, C, shape = cfg.rms_norm_eps, cfg.hidden_size, tokens.shape
    x = params["wte"].astype(cfg.dtype)[tokens.reshape(-1)]
    X = jnp.tile(x, (1, cfg.hc_mult))  # every stream starts as the token
    rows, counts = [], []
    for i, p in enumerate(params["layers"]):
        pre, post, res = mhc_coefficients(X, p["hc_attn"], cfg)
        h = mla.rmsnorm(mhc_pre(X, pre, cfg), p["attn_norm"], eps)
        with jax.named_scope("attn.latent"):
            y, latent = attention(h.reshape(*shape, C), p, i)
        rows.append(latent)
        X = mhc_post(X, y.reshape(-1, C), post, res, cfg)
        pre, post, res = mhc_coefficients(X, p["hc_ffn"], cfg)
        h = mla.rmsnorm(mhc_pre(X, pre, cfg), p["ffn_norm"], eps)
        if i >= cfg.first_k_dense_replace:
            y, c = mla.experts(h, p, cfg)
            counts.append(c)
        else:
            y = mla.dense(h, p, cfg)
        X = mhc_post(X, y, post, res, cfg)
    with jax.named_scope("resid.mhc.exit"):
        x = sum(_streams(X, cfg)).astype(cfg.dtype)
    x = mla.rmsnorm(x, params["lnf"], eps)
    logits = (x @ params["lm_head"].astype(cfg.dtype)).astype(jnp.float32)
    rows = jnp.stack(rows)[..., None, :]
    return (logits.reshape(*shape, -1), rows,
            jnp.zeros(rows.shape[:-1] + (0,), rows.dtype), jnp.stack(counts))


# --------------------------------------------------------------------------
# KV-cache inference steps (serve.llm): the model owns the mathematics,
# serve/llm/runner.py the pages.


def xing4_prefill_kv(params: Params, tokens: jax.Array, cfg: Xing4Config):
    """A whole prompt from position 0: tokens (1, T) -> (logits (1, T,
    Vp) f32, latent rows (L, 1, T, 1, latent_row), rows of no lanes (L, 1,
    T, 1, 0), pairs (expert layers, n_routed_experts))."""
    B, T = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(T), (B, T))
    seen = jnp.broadcast_to(jnp.tril(jnp.ones((T, T), bool)), (B, T, T))

    def attention(h, p, i):
        return _attend_rows(h, p, positions, seen, cfg)

    return _stack(params, tokens, cfg, attention)


def xing4_prefill_chunk_kv(params: Params, tokens: jax.Array, start, ctx,
                           chunk_mask, cfg: Xing4Config):
    """A chunk at positions start..start+T-1: `ctx` is the cached context
    for positions < start."""
    B, T = tokens.shape
    positions = start + jnp.broadcast_to(jnp.arange(T), (B, T))
    own = causal_rows(chunk_mask)

    def attention(h, p, i):
        return _attend_cached(h, p, positions, own, ctx, i, cfg)

    return _stack(params, tokens, cfg, attention)


def xing4_decode_kv(params: Params, tokens: jax.Array, positions, ctx,
                    cfg: Xing4Config):
    """One token a lane: tokens (B,) at `positions`, against the lanes'
    cached context -> (logits (B, Vp) f32, latent rows (L, B, 1,
    latent_row), rows of no lanes, pairs)."""
    B = tokens.shape[0]
    own = jnp.ones((B, 1, 1), bool)

    def attention(h, p, i):
        y, latent = _attend_cached(h[:, None], p, positions[:, None], own,
                                   ctx, i, cfg)
        return y[:, 0], latent[:, 0]

    return _stack(params, tokens, cfg, attention)
