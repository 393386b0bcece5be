"""glm_dsa: latent attention (MLA) whose cached context is chosen, row by
row, by a learned indexer (DSA), a few leading dense feed-forwards, then
routed experts beside a shared one.

Fifth model family beside gpt2, llama, nemotron_h and mimo_v2, after
Z.ai's GLM-5 (`model_type` glm_moe_dsa; its keys are DeepSeek-V3.2's).
Every block is ``x = x + attn(rmsnorm(x)); x = x + ffn(rmsnorm(x))`` and
every block's attention is the same (its latent-attention parts and
the feed-forwards are written in models/mla.py, which the xing4 family
runs too; the indexer and the two ways through the attention are here):

- **queries**: ``c_q = rmsnorm(h W_qa)`` (`q_lora_rank`), ``q = c_q W_qb``
  -> `num_attention_heads` heads of ``qk_nope_head_dim | qk_rope_head_dim``;
  the second part is rotated (`rope_theta`, **interleaved** pairs ``(2i,
  2i + 1)``);
- **keys and values**: ``[c_kv | k_pe] = h W_kva`` (`kv_lora_rank` |
  `qk_rope_head_dim`); ``c_kv = rmsnorm(c_kv)``, `k_pe` rotated, one for
  all heads; ``[k_nope | v] = c_kv W_kvb`` a head. Scores ``(q_nope .
  k_nope + q_pe . k_pe) / sqrt(qk_nope_head_dim + qk_rope_head_dim)``;
- the **latent row** ``[c_kv | k_pe]`` is all that is cached of a token
  and layer. Against it the attention is **absorbed**: ``q_nope W_kvb[k]^T``
  is a `kv_lora_rank`-wide query on `c_kv`, the value is `c_kv`, and
  ``W_kvb[v]`` is applied after the softmax: every head shares the one
  row. A whole prompt's own rows are up-projected instead (`_attend_rows`);
  the two are equal in exact arithmetic and a test holds them together;
- the **indexer**: ``q^I = c_q W^I_qb`` -> `index_n_heads` heads of
  `index_head_dim`, ``k^I = layernorm(h W^I_k)`` (one for all heads), the
  first `qk_rope_head_dim` lanes of both rotated (interleaved), ``w = h
  W^I_w``; ``I[t, s] = sum_j w[t, j] relu(q^I[t, j] . k^I[s]) / sqrt(heads
  * width)``. Row t attends to the `index_topk` slots ``s <= t`` of largest
  ``I[t, s]`` (all of them while there are no more) and to no other. `k^I`
  is the second row cached of a token and layer;
- layers below `first_k_dense_replace` have a **dense** SwiGLU
  feed-forward, the others **routed experts** (models/moe.py): a sigmoid
  router with a selection bias (`noaux_tc`; one group), weights
  normalised to one and times `routed_scaling_factor`, and a shared
  expert on every row. `experts_held` and `expert_offset` say which of
  the router's experts this chip holds.

One kind of KV layer (serve/llm/cache.py `KVKind`, `select` set): the
forwards return the latent rows where another family returns k and the
indexer keys where it returns v, and take their cached context as one
`CachedContext` whose two pools hold the two. The multi-token-prediction
layer is not part of this model. Matrix products are in `dtype`; norms,
rotation, index scores, softmax and router are float32.

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
from ray_tpu.models.mla import (
    dense as _dense,
    experts as _experts,
    rmsnorm as _rmsnorm,
    swiglu as _swiglu,  # noqa: F401 - the tests' name for it
)
from ray_tpu.ops.context_attention import (
    attend_selected,
    causal_rows,
    index_scores,
    select_mask,
    softmax_over,
)
from ray_tpu.parallel.sharding import PartitionRules

Params = Any


@dataclasses.dataclass(frozen=True)
class GlmDsaConfig:
    """Field names are the published config.json's, but for those that
    say what is held here and the seeded weights' spread."""

    vocab_size: int = 154880
    hidden_size: int = 6144
    num_hidden_layers: int = 78
    first_k_dense_replace: int = 3
    # attention
    num_attention_heads: int = 64
    q_lora_rank: int = 2048
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 192
    qk_rope_head_dim: int = 64
    v_head_dim: int = 256
    rope_theta: float = 1e6
    # indexer
    index_n_heads: int = 32
    index_head_dim: int = 128
    index_topk: int = 2048
    # lanes of zeros behind ``[c_kv | k_pe]`` in the cached row: 576 lanes
    # are 4.5 lane tiles, which XLA:TPU lays out padded and copies the
    # pool around every program for; 640 it leaves where they are
    # (tests/test_kv_pool_layout.py)
    latent_pad: int = 64
    # feed-forward
    intermediate_size: int = 12288
    moe_intermediate_size: int = 2048
    n_routed_experts: int = 256
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    experts_held: int = 256  # of n_routed_experts, from expert_offset on
    expert_offset: int = 0
    rms_norm_eps: float = 1e-5
    initializer_range: float = 0.02  # std of a seeded matrix
    max_position_embeddings: int = 202752
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16  # what `init_glm_dsa` creates

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
        return 1.0 / math.sqrt(self.qk_head_dim)

    def rotate(self, x, positions):
        return _rope(x, positions, self.rope_theta, self.qk_rope_head_dim)

    @property
    def latent_row(self) -> int:
        """Lanes of the row cached a token and layer: ``[c_kv | k_pe |
        zeros]``."""
        return self.kv_lora_rank + self.qk_rope_head_dim + self.latent_pad

    def kv_kinds(self) -> tuple[tuple, ...]:
        """The one kind of KV layer, the fields of a serve/llm/cache.py
        `KVKind`: one head whose K row is the latent row, whose second
        row is the indexer's key, no window, `index_topk` slots chosen."""
        return (("latent", self.n_layer, 1, self.latent_row,
                 self.index_head_dim, None, self.index_topk),)

    def n_params(self) -> int:
        """Parameters of the tree at `vocab_size` rows (the padding rows
        of the embedding and the head not counted)."""
        D, H = self.hidden_size, self.num_attention_heads
        attn = (D * self.q_lora_rank + self.q_lora_rank * H * self.qk_head_dim
                + D * (self.kv_lora_rank + self.qk_rope_head_dim)
                + self.kv_lora_rank * H * (self.qk_nope_head_dim
                                           + self.v_head_dim)
                + H * self.v_head_dim * D
                + self.q_lora_rank * self.index_n_heads * self.index_head_dim
                + D * self.index_head_dim + D * self.index_n_heads
                + D + self.q_lora_rank + self.kv_lora_rank
                + 2 * self.index_head_dim)
        dense = 3 * D * self.intermediate_size + D
        expert = 3 * D * self.moe_intermediate_size
        routed = (D * self.n_routed_experts + self.n_routed_experts
                  + (self.experts_held + 1) * expert + D)
        n_dense = self.first_k_dense_replace
        return (self.n_layer * attn + n_dense * dense
                + (self.n_layer - n_dense) * routed
                + 2 * self.vocab_size * D + D)

    @staticmethod
    def tiny() -> "GlmDsaConfig":
        """Every mechanism at a size for CPU tests, float32: 16 slots
        chosen, 16 experts of which 4 (from the 4th on) are held."""
        return GlmDsaConfig(
            vocab_size=512, hidden_size=64, num_hidden_layers=3,
            first_k_dense_replace=1, num_attention_heads=4, q_lora_rank=32,
            kv_lora_rank=24, qk_nope_head_dim=12, qk_rope_head_dim=8,
            v_head_dim=16, index_n_heads=4, index_head_dim=16, index_topk=16,
            latent_pad=8,
            intermediate_size=96, moe_intermediate_size=32,
            n_routed_experts=16, num_experts_per_tok=3, experts_held=4,
            expert_offset=4,
            max_position_embeddings=256, dtype=jnp.float32,
            param_dtype=jnp.float32)

    @staticmethod
    def glm_5() -> "GlmDsaConfig":
        """GLM-5 as published (huggingface.co/zai-org/GLM-5, config.json):
        78 blocks of 6144, every expert held (1.5 TB in bf16: the base of
        the cut below, served nowhere here)."""
        return GlmDsaConfig()

    @staticmethod
    def glm_5_l5_ep32() -> "GlmDsaConfig":
        """One chip's share where thirty-two chips share each layer: layer
        0 (dense; the three leading dense layers count once) and expert
        layers 3-6, 8 of the 256 experts and 19,360 of the 154,880
        vocabulary rows; every width as published (PERF.md section 4)."""
        return dataclasses.replace(
            GlmDsaConfig.glm_5(), num_hidden_layers=5,
            first_k_dense_replace=1, experts_held=8, vocab_size=19360,
            max_position_embeddings=16768)


def glm_dsa_partition_rules() -> PartitionRules:
    """The held experts over `expert`; the vocabulary over `tensor`;
    attention, indexer, router, shared expert and the dense feed-forward
    whole on every device, as the stated deployment has it (a latent row
    cannot be split by head)."""
    from jax.sharding import PartitionSpec as P

    return PartitionRules([
        (r"layers/\d+/(we_gate|we_up|we_down)$", P("expert", None, None)),
        (r"wte$", P("tensor", None)),
        (r"lm_head$", P(None, "tensor")),
        (r".*", P()),
    ])


@functools.partial(jax.jit, static_argnames=("cfg",))
def init_glm_dsa(key: jax.Array, cfg: GlmDsaConfig) -> Params:
    """One program for the whole tree, every leaf drawn in float32 and
    written in `cfg.param_dtype` by the same fusion. Matrices are normal
    with std `initializer_range`, those that write the residual stream
    that over sqrt(L); norm scales 1, the indexer's LayerNorm bias 0 (the
    index scores are sums of `index_n_heads` continuous terms: they
    spread, and no two tied at the rank that is cut in any reading).
    `wkv_b` is held as its two
    column groups, `wk_b` (rank, heads, nope) and `wv_b` (rank, heads,
    v), so that neither program slices a weight."""
    L, D, V = cfg.n_layer, cfg.hidden_size, cfg.padded_vocab
    H, R = cfg.num_attention_heads, cfg.kv_lora_rank
    pdt = cfg.param_dtype
    std = cfg.initializer_range
    out_std = std / math.sqrt(L)
    k_wte, k_head, k_layers = jax.random.split(key, 3)

    def normal(k, shape, scale):
        return (jax.random.normal(k, shape, jnp.float32) * scale).astype(pdt)

    def attention(k):
        ks = jax.random.split(k, 9)
        return {
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
            "wiq_b": normal(ks[6], (cfg.q_lora_rank,
                                    cfg.index_n_heads * cfg.index_head_dim),
                            std),
            "wik": normal(ks[7], (D, cfg.index_head_dim), std),
            "ik_norm": jnp.ones((cfg.index_head_dim,), pdt),
            "ik_bias": jnp.zeros((cfg.index_head_dim,), pdt),
            "wiw": normal(ks[8], (D, cfg.index_n_heads), std),
        }

    def feed_forward(k, routed):
        ks = jax.random.split(k, 8)
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
# the layer's parts: latent attention's and the feed-forwards' are
# models/mla.py's, which the xing4 family runs too; the indexer's are here


def _layernorm(x, scale, bias, eps):
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean((x32 - mu) ** 2, axis=-1, keepdims=True)
    return ((x32 - mu) * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)
            + bias.astype(jnp.float32)).astype(x.dtype)


def _rope(x, positions, theta: float, width: int):
    return mla.rope(x, positions, mla.plain_frequencies(theta, width), width)


def _indexer(h, c_q, p, positions, cfg: GlmDsaConfig):
    """-> (q^I (..., HI, Di) and k^I (..., Di), both rotated in their
    first lanes, w (..., HI) float32 with the score's scale in it)."""
    dt = cfg.dtype
    HI, Di = cfg.index_n_heads, cfg.index_head_dim
    with jax.named_scope("attn.index.qk"):
        qi = (c_q @ p["wiq_b"].astype(dt)).reshape(*h.shape[:-1], HI, Di)
        ki = _layernorm(h @ p["wik"].astype(dt), p["ik_norm"], p["ik_bias"],
                        cfg.rms_norm_eps)
        qi = _rope(qi, positions, cfg.rope_theta, cfg.qk_rope_head_dim)
        ki = _rope(ki, positions, cfg.rope_theta, cfg.qk_rope_head_dim)
        w = (h @ p["wiw"].astype(dt)).astype(jnp.float32) \
            * (HI ** -0.5 * Di ** -0.5)
    return qi, ki, w


def _projections(h, p, positions, cfg: GlmDsaConfig):
    """What both attention paths start from: (q_nope, q_pe, the latent
    rows, q^I, k^I, w)."""
    q_nope, q_pe, c_q = mla.queries(h, p, positions, cfg)
    return (q_nope, q_pe, mla.latent(h, p, positions, cfg),
            *_indexer(h, c_q, p, positions, cfg))


def _attend_rows(h, p, positions, seen, cfg: GlmDsaConfig):
    """A whole prompt's own rows h (B, T, D), nothing cached: the latent
    rows up-projected to a K and a V head each, the indexer's choice
    among the rows `seen` (B, T, T) allows as the softmax's mask. ->
    (out (B, T, D), latent rows, indexer keys)."""
    q_nope, q_pe, latent, qi, ki, w = _projections(h, p, positions, cfg)
    with jax.named_scope("attn.index.score"):
        scores = index_scores(qi, ki, w, seen)
    with jax.named_scope("attn.index.topk"):
        chosen = select_mask(scores, cfg.index_topk)
    with jax.named_scope("attn.mla.core"):
        k, v = mla.up_project(latent, q_pe, p, cfg)
        q = jnp.concatenate([q_nope, q_pe], axis=-1)
        att = softmax_over(q[:, :, :, None], [(k, v, chosen)],
                           cfg.softmax_scale, cfg.dtype)[:, :, :, 0]
    return mla.output(att, p, cfg), latent, ki


def _attend_latent(h, p, positions, own_valid, ctx, layer,
                   cfg: GlmDsaConfig, with_choice: bool = False):
    """Rows h (B, T, D) of a chunk or a decode step against the lanes'
    cached latent rows and their own, absorbed: every head's query on the
    one latent row, ``W_kvb[v]`` after the softmax. -> (out (B, T, D),
    latent rows, indexer keys[, the rows' choice, `attend_selected`'s])."""
    q_nope, q_pe, latent, qi, ki, w = _projections(h, p, positions, cfg)
    q = mla.absorbed_query(q_nope, q_pe, p, cfg)
    att = attend_selected(
        q, latent, qi, ki, w, own_valid, ctx, layer, cfg.dtype,
        values=cfg.kv_lora_rank, scale=cfg.softmax_scale,
        with_choice=with_choice)
    att, *choice = att if with_choice else (att,)
    return (mla.values_out(att, p, cfg), latent, ki, *choice)


def _stack(params, x, cfg: GlmDsaConfig, attention):
    """The blocks on x (B, T, D) or (B, D). ``attention(h, p, i) -> (out,
    latent rows, indexer keys)`` is the program's way through layer i's
    attention; the feed-forwards are the same in every program. Returns
    (logits f32, latent rows and indexer keys stacked over the layers
    with a head dimension of 1, as the pools take them, pairs per expert
    stacked over the expert layers)."""
    eps = cfg.rms_norm_eps
    rows, keys, counts = [], [], []
    for i, p in enumerate(params["layers"]):
        with jax.named_scope("attn.latent"):
            y, latent, ki = attention(_rmsnorm(x, p["attn_norm"], eps), p, i)
        rows.append(latent)
        keys.append(ki)
        x = x + y
        h = _rmsnorm(x, p["ffn_norm"], eps)
        if i >= cfg.first_k_dense_replace:
            y, c = _experts(h.reshape(-1, h.shape[-1]), p, cfg)
            y = y.reshape(h.shape)
            counts.append(c)
        else:
            y = _dense(h, p, cfg)
        x = x + y
    x = _rmsnorm(x, params["lnf"], eps)
    logits = (x @ params["lm_head"].astype(cfg.dtype)).astype(jnp.float32)
    return (logits, jnp.stack(rows)[..., None, :],
            jnp.stack(keys)[..., None, :], jnp.stack(counts))


# --------------------------------------------------------------------------
# KV-cache inference steps (serve.llm): the model owns the mathematics,
# serve/llm/runner.py the pages.


def glm_dsa_prefill_kv(params: Params, tokens: jax.Array,
                       cfg: GlmDsaConfig):
    """A whole prompt from position 0: tokens (1, T) -> (logits (1, T,
    Vp) f32, latent rows (L, 1, T, 1, latent_row), indexer keys (L, 1,
    T, 1, index_head_dim), pairs (expert layers, n_routed_experts))."""
    B, T = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(T), (B, T))
    seen = jnp.broadcast_to(jnp.tril(jnp.ones((T, T), bool)), (B, T, T))

    def attention(h, p, i):
        return _attend_rows(h, p, positions, seen, cfg)

    x = params["wte"].astype(cfg.dtype)[tokens]
    return _stack(params, x, cfg, attention)


def glm_dsa_prefill_chunk_kv(params: Params, tokens: jax.Array, start,
                             ctx, chunk_mask, cfg: GlmDsaConfig):
    """A chunk at positions start..start+T-1: `ctx` is the cached context
    for positions < start."""
    B, T = tokens.shape
    positions = start + jnp.broadcast_to(jnp.arange(T), (B, T))
    own = causal_rows(chunk_mask)

    def attention(h, p, i):
        return _attend_latent(h, p, positions, own, ctx, i, cfg)

    x = params["wte"].astype(cfg.dtype)[tokens]
    return _stack(params, x, cfg, attention)


def glm_dsa_decode_kv(params: Params, tokens: jax.Array, positions, ctx,
                      cfg: GlmDsaConfig):
    """One token a lane: tokens (B,) at `positions`, against the lanes'
    cached context -> (logits (B, Vp) f32, latent rows (L, B, 1,
    latent_row), indexer keys (L, B, 1, index_head_dim), pairs)."""
    B = tokens.shape[0]
    own = jnp.ones((B, 1, 1), bool)

    def attention(h, p, i):
        y, latent, ki = _attend_latent(h[:, None], p, positions[:, None],
                                       own, ctx, i, cfg)
        return y[:, 0], latent[:, 0], ki[:, 0]

    x = params["wte"].astype(cfg.dtype)[tokens]
    return _stack(params, x, cfg, attention)
