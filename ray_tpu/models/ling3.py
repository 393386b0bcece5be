"""ling3: linear-attention layers (Kimi Delta Attention) with a latent-
attention layer every `layer_group_size`-th, a few leading dense
feed-forwards, then routed experts under a group limit beside a shared one.

Ninth served family, after inclusionAI's Ling-3.0-flash language model
(the config.json of Ling-3.0-flash-VL; its vision tower and its MTP module
are not served). Pre-norm, a residual around each half:

    x = x + mixer(rmsnorm(x));  x = x + feed_forward(rmsnorm(x))

- layer i's **mixer** is latent attention where ``(i + 1) %
  layer_group_size == 0`` and KDA otherwise (`layer_types`; 35 of 42):
  - ``kda`` (models/kda.py): q, k, v through a short causal convolution
    and SiLU, q and k of unit L2 norm, a state ``S`` (128, 128) float32 a
    head that forgets by a decay a channel (``-5 sigmoid(.)`` a row: the
    safe gate) and learns by the delta rule, an RMSNorm a head under a
    head-wise sigmoid gate;
  - ``mla`` (models/mla.py, DeepSeek-V3's layer as glm_dsa and xing4 run
    it) with NO low-rank query (``q = h W_q``), 64 rotated lanes at plain
    frequencies (`rope_theta` 6e6, interleaved pairs), no indexer: a row
    attends every earlier slot. A whole prompt's own rows are
    up-projected, a chunk's and a decode step's attend absorbed to the
    cached latent rows (`mla.attend_rows`, `mla.attend_cached`);
- **feed-forward**: layers below `first_k_dense_replace` a dense SwiGLU,
  the others routed experts (models/moe.py): sigmoid scores, a selection
  bias, the experts in `n_group` groups of which a row's `topk_group`
  best (by their two largest biased scores) can be chosen from, weights
  normalised and times `routed_scaling_factor`, the shared expert on
  every row; `experts_held` and `expert_offset` say which of the
  router's experts this chip holds.

Two kinds of cached state a lane (serve/llm/cache.py): the latent layers'
rows in pages (a `KVKind` with a `v_head_dim` of 0, as xing4's), and a KDA
layer's recurrent state in the lane's slot (`KdaSizes.state_parts`: three
conv rows in `dtype`, the matrix a head in float32).

Matrix products with weights are in `dtype`; the KDA state and everything
inside its recurrence, norms, rotation, softmax and router are float32.
Nothing is built when this module is imported.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any

import jax
import jax.numpy as jnp

from ray_tpu.models import kda, mla
from ray_tpu.ops.context_attention import causal_rows
from ray_tpu.parallel.sharding import PartitionRules

Params = Any

KDA, MLA = "kda", "mla"


@dataclasses.dataclass(frozen=True)
class Ling3Config:
    """Field names are the published config.json's, but for `layer_types`
    (the published rule written out, so that a cut can keep layers that
    are not the first), `latent_pad`, the two that say what is held here
    and the seeded weights' spread."""

    vocab_size: int = 157184
    hidden_size: int = 2560
    num_hidden_layers: int = 42
    first_k_dense_replace: int = 2
    layer_group_size: int = 6
    # None: layer i is MLA where (i + 1) % layer_group_size == 0
    layer_types: tuple | None = None
    # both mixers
    num_attention_heads: int = 32
    head_dim: int = 128
    # kda
    short_conv_kernel_size: int = 4
    kda_lower_bound: float = -5.0
    # mla
    q_lora_rank: int | None = None
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 6e6
    # lanes of zeros behind ``[c_kv | k_pe]`` in the cached row, as
    # xing4's and for its reason (tests/test_kv_pool_layout.py)
    latent_pad: int = 64
    # feed-forward
    intermediate_size: int = 6144
    moe_intermediate_size: int = 768
    moe_shared_expert_intermediate_size: int = 768
    num_experts: int = 512
    num_experts_per_tok: int = 8
    n_group: int = 8
    topk_group: int = 4
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    experts_held: int = 512  # of num_experts, from expert_offset on
    expert_offset: int = 0
    rms_norm_eps: float = 1e-6
    initializer_range: float = 0.02  # std of a seeded matrix
    max_position_embeddings: int = 131072
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16  # what `init_ling3` creates

    def __post_init__(self):
        if self.expert_offset + self.experts_held > self.num_experts:
            raise ValueError("experts held lie outside the router's range")
        if self.num_experts % self.n_group:
            raise ValueError("n_group does not divide the experts")
        if len(self.kinds) != self.num_hidden_layers \
                or set(self.kinds) - {KDA, MLA}:
            raise ValueError(f"layer_types {self.layer_types!r}: one of "
                             f"{KDA!r}, {MLA!r} a layer")
        if self.first_k_dense_replace > self.num_hidden_layers:
            raise ValueError("more leading dense layers than layers")

    @property
    def kinds(self) -> tuple:
        """The mixer of every layer."""
        if self.layer_types is not None:
            return tuple(self.layer_types)
        return tuple(MLA if (i + 1) % self.layer_group_size == 0 else KDA
                     for i in range(self.num_hidden_layers))

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
    def n_kv_layers(self) -> int:
        return self.kinds.count(MLA)

    @property
    def n_kda_layers(self) -> int:
        return self.kinds.count(KDA)

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def softmax_scale(self) -> float:
        return 1.0 / math.sqrt(self.qk_head_dim)

    def rotate(self, x, positions):
        with jax.named_scope("attn.rope"):
            return mla.rope(x, positions, mla.plain_frequencies(
                self.rope_theta, self.qk_rope_head_dim),
                self.qk_rope_head_dim)

    @property
    def latent_row(self) -> int:
        """Lanes of the row cached a token and MLA layer: ``[c_kv | k_pe |
        zeros]``."""
        return self.kv_lora_rank + self.qk_rope_head_dim + self.latent_pad

    @property
    def kda(self) -> kda.KdaSizes:
        """What the KDA mixer (models/kda.py) asks of a family."""
        return kda.KdaSizes(
            heads=self.num_attention_heads, head_dim=self.head_dim,
            conv_kernel=self.short_conv_kernel_size,
            lower_bound=self.kda_lower_bound, eps=self.rms_norm_eps,
            dtype=self.dtype)

    def state_parts(self) -> tuple:
        """(name, shape a lane and layer, dtype) of a KDA layer's
        recurrent state, for `cache.StateLayout`."""
        return self.kda.state_parts()

    def kv_kinds(self) -> tuple[tuple, ...]:
        """The one kind of KV layer, the fields of a serve/llm/cache.py
        `KVKind`: one head whose K row is the latent row, no second row
        (a latent kind with no indexer), no window, nothing chosen."""
        return (("latent", self.n_kv_layers, 1, self.latent_row, 0, None,
                 None),)

    def n_params(self) -> int:
        """Parameters of the tree at `vocab_size` rows (the padding rows
        of the embedding and the head not counted)."""
        D, H = self.hidden_size, self.num_attention_heads
        s = self.kda
        mixer = {
            KDA: (D * (s.conv_dim + s.d_inner + 2 * H)
                  + s.conv_kernel * s.conv_dim + s.d_inner + H
                  + s.head_dim + s.d_inner * D + D),
            MLA: (D * H * self.qk_head_dim
                  + D * (self.kv_lora_rank + self.qk_rope_head_dim)
                  + self.kv_lora_rank * H * (self.qk_nope_head_dim
                                             + self.v_head_dim)
                  + H * self.v_head_dim * D + D + self.kv_lora_rank)}
        dense = 3 * D * self.intermediate_size + D
        routed = (D * self.num_experts + self.num_experts
                  + 3 * D * (self.experts_held * self.moe_intermediate_size
                             + self.moe_shared_expert_intermediate_size)
                  + D)
        n_dense = self.first_k_dense_replace
        return (sum(mixer[kind] for kind in self.kinds) + n_dense * dense
                + (self.n_layer - n_dense) * routed
                + 2 * self.vocab_size * D + D)

    @staticmethod
    def tiny() -> "Ling3Config":
        """Every mechanism at a size for CPU tests, float32: two KDA
        layers to every MLA layer, 16 experts in 4 groups of which 2, 4
        experts (from the 4th on) held."""
        return Ling3Config(
            vocab_size=512, hidden_size=64, num_hidden_layers=4,
            first_k_dense_replace=1, layer_group_size=3,
            num_attention_heads=4, head_dim=16, kv_lora_rank=24,
            qk_nope_head_dim=12, qk_rope_head_dim=8, v_head_dim=16,
            rope_theta=1e4, latent_pad=8, intermediate_size=96,
            moe_intermediate_size=32,
            moe_shared_expert_intermediate_size=32, num_experts=16,
            num_experts_per_tok=3, n_group=4, topk_group=2,
            experts_held=4, expert_offset=4, initializer_range=0.1,
            max_position_embeddings=512, dtype=jnp.float32,
            param_dtype=jnp.float32)

    @staticmethod
    def flash() -> "Ling3Config":
        """Ling-3.0-flash's language model as published
        (huggingface.co/inclusionAI/Ling-3.0-flash-VL, config.json): 42
        layers of 2560, every expert held (some 250 GB in bf16: the base
        of the cut below, served nowhere here)."""
        return Ling3Config()

    @staticmethod
    def flash_l7_ep32() -> "Ling3Config":
        """One chip's share where 32 chips share each layer: layer 0 (KDA,
        dense; the two leading dense layers count once) and layers 6-11
        (five KDA and the MLA layer that closes their period, all with
        experts), 16 of the 512 experts and 19,648 of the 157,184
        vocabulary rows; every width as published (PERF.md section 4)."""
        return dataclasses.replace(
            Ling3Config.flash(), num_hidden_layers=7,
            first_k_dense_replace=1, layer_types=(KDA,) * 6 + (MLA,),
            experts_held=16, vocab_size=19648,
            max_position_embeddings=18688)


def ling3_partition_rules() -> PartitionRules:
    """The held experts over `expert`; the vocabulary over `tensor`; both
    mixers, router, shared expert and the dense feed-forward whole on
    every device, as the stated deployment has it (neither a latent row
    nor a KDA state is split by head without an exchange)."""
    from jax.sharding import PartitionSpec as P

    return PartitionRules([
        (r"layers/\d+/(we_gate|we_up|we_down)$", P("expert", None, None)),
        (r"wte$", P("tensor", None)),
        (r"lm_head$", P(None, "tensor")),
        (r".*", P()),
    ])


@functools.partial(jax.jit, static_argnames=("cfg",))
def init_ling3(key: jax.Array, cfg: Ling3Config) -> Params:
    """One program for the whole tree, every leaf drawn in float32 and
    written in `cfg.param_dtype` by the same fusion. Matrices are normal
    with std `initializer_range`, those that write the residual stream
    that over sqrt(L); norm scales 1; the router's bias normal with std
    0.02. A KDA layer's gate is drawn so that the recurrence is neither
    memoryless nor an accumulator: `dt_bias` uniform in -5..-1 and
    `A_log` the log of uniform 0.5..1.5, so that ``exp(g)`` a row spreads
    over 0.15..0.997 (a channel remembers one row or three hundred) with
    the token's own part (``h W_f``, std about 1 at `initializer_range`
    0.02 and 2560 inputs) moving it; beta's columns at
    twice the std, so that beta spreads over (0, 1); the conv as torch's
    Conv1d default (uniform within 1 / sqrt(K)), no bias. `wkv_b` is held
    as its two column groups, `wk_b` and `wv_b`, as xing4's."""
    L, D, V = cfg.n_layer, cfg.hidden_size, cfg.padded_vocab
    H, R = cfg.num_attention_heads, cfg.kv_lora_rank
    pdt = cfg.param_dtype
    std = cfg.initializer_range
    out_std = std / math.sqrt(L)
    k_wte, k_head, k_layers = jax.random.split(key, 3)

    def normal(k, shape, scale):
        return (jax.random.normal(k, shape, jnp.float32) * scale) \
            .astype(pdt)

    def uniform(k, shape, lo, hi):
        return jax.random.uniform(k, shape, jnp.float32, lo, hi)

    def kda_mixer(k):
        ks = jax.random.split(k, 6)
        s = cfg.kda
        bound = 1.0 / math.sqrt(s.conv_kernel)
        wide = s.conv_dim + s.d_inner  # q~ | k~ | v~ | f
        scale = jnp.concatenate([jnp.full((wide,), std),
                                 jnp.full((H,), 2 * std),  # beta's
                                 jnp.full((H,), std)])  # the output gate's
        return {
            "in_proj": (jax.random.normal(ks[0], (D, wide + 2 * H),
                                          jnp.float32) * scale).astype(pdt),
            "conv_w": uniform(ks[1], (s.conv_kernel, s.conv_dim), -bound,
                              bound).astype(pdt),
            "A_log": jnp.log(uniform(ks[2], (H,), 0.5, 1.5)).astype(pdt),
            "dt_bias": uniform(ks[3], (s.d_inner,), -5.0, -1.0).astype(pdt),
            "o_norm": jnp.ones((s.head_dim,), pdt),
            "out_proj": normal(ks[4], (s.d_inner, D), out_std),
        }

    def mla_mixer(k):
        ks = jax.random.split(k, 5)
        return {
            "wq": normal(ks[0], (D, H * cfg.qk_head_dim), std),
            "wkv_a": normal(ks[1], (D, R + cfg.qk_rope_head_dim), std),
            "kv_norm": jnp.ones((R,), pdt),
            "wk_b": normal(ks[2], (R, H, cfg.qk_nope_head_dim), std),
            "wv_b": normal(ks[3], (R, H, cfg.v_head_dim), std),
            "wo": normal(ks[4], (H * cfg.v_head_dim, D), out_std),
        }

    def feed_forward(k, routed):
        ks = jax.random.split(k, 8)
        if not routed:
            F = cfg.intermediate_size
            return {"w_gate": normal(ks[0], (D, F), std),
                    "w_up": normal(ks[1], (D, F), std),
                    "w_down": normal(ks[2], (F, D), out_std)}
        X, F = cfg.experts_held, cfg.moe_intermediate_size
        Fs = cfg.moe_shared_expert_intermediate_size
        return {"router": normal(ks[3], (D, cfg.num_experts), std),
                "router_bias": normal(ks[4], (cfg.num_experts,), 0.02),
                "we_gate": normal(ks[0], (X, D, F), std),
                "we_up": normal(ks[1], (X, D, F), std),
                "we_down": normal(ks[2], (X, F, D), out_std),
                "ws_gate": normal(ks[5], (D, Fs), std),
                "ws_up": normal(ks[6], (D, Fs), std),
                "ws_down": normal(ks[7], (Fs, D), out_std)}

    make = {KDA: kda_mixer, MLA: mla_mixer}
    layers = []
    for i, (kind, k) in enumerate(zip(cfg.kinds,
                                      jax.random.split(k_layers, L))):
        km, kf = jax.random.split(k)
        layers.append({"mixer_norm": jnp.ones((D,), pdt), **make[kind](km),
                       "ffn_norm": jnp.ones((D,), pdt),
                       **feed_forward(kf, i >= cfg.first_k_dense_replace)})
    return {"wte": normal(k_wte, (V, D), std), "layers": layers,
            "lnf": jnp.ones((D,), pdt),
            "lm_head": normal(k_head, (D, V), std)}


def _stack(params, tokens, cfg: Ling3Config, linear, attention):
    """The layers in `cfg.kinds`' order on the embedded tokens (B, T) or
    (B,). ``linear(h, p, i)`` and ``attention(h, p, i) -> (out, latent
    rows)`` are the program's way through the two mixers, `i` counting
    the layers of that kind; the feed-forwards are the same in every
    program. Returns (logits f32, the latent rows stacked over the MLA
    layers with a head dimension of 1, as the pool takes them, rows of no
    lanes for the pool the kind has not, pairs per expert stacked over
    the expert layers)."""
    eps, D = cfg.rms_norm_eps, cfg.hidden_size
    x = params["wte"].astype(cfg.dtype)[tokens]
    seen = {KDA: 0, MLA: 0}
    rows, counts = [], []
    for i, (kind, p) in enumerate(zip(cfg.kinds, params["layers"])):
        h = mla.rmsnorm(x, p["mixer_norm"], eps)
        if kind == KDA:
            with jax.named_scope("attn.kda"):
                y = linear(h, p, seen[kind])
        else:
            with jax.named_scope("attn.latent"):
                y, latent = attention(h, p, seen[kind])
            rows.append(latent)
        seen[kind] += 1
        x = x + y
        h = mla.rmsnorm(x, p["ffn_norm"], eps)
        if i >= cfg.first_k_dense_replace:
            y, c = mla.experts(h.reshape(-1, D), p, cfg)
            counts.append(c)
            y = y.reshape(h.shape)
        else:
            y = mla.dense(h, p, cfg)
        x = x + y
    x = mla.rmsnorm(x, params["lnf"], eps)
    logits = (x @ params["lm_head"].astype(cfg.dtype)).astype(jnp.float32)
    rows = jnp.stack(rows)[..., None, :]
    return (logits, rows, jnp.zeros(rows.shape[:-1] + (0,), rows.dtype),
            jnp.stack(counts))


# --------------------------------------------------------------------------
# KV-cache and state inference steps (serve.llm): the models own the
# mathematics, serve/llm/runner.py the pages, `state` (a cache.StateView)
# the recurrent state's reads and writes.


def ling3_prefill_kv(params: Params, tokens: jax.Array, cfg: Ling3Config,
                     *, state, n_valid):
    """A whole prompt from position 0: tokens (1, T), of which the first
    `n_valid` are real -> (logits (1, T, Vp) f32, latent rows (MLA layers,
    1, T, 1, latent_row), rows of no lanes, pairs (expert layers,
    num_experts))."""
    B, T = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(T), (B, T))
    seen = jnp.broadcast_to(jnp.tril(jnp.ones((T, T), bool)), (B, T, T))

    def linear(h, p, i):
        return kda.rows(h[0], p, cfg.kda, state, i, n_valid)[None]

    def attention(h, p, i):
        return mla.attend_rows(h, p, positions, seen, cfg)

    return _stack(params, tokens, cfg, linear, attention)


def ling3_prefill_chunk_kv(params: Params, tokens: jax.Array, start, ctx,
                           chunk_mask, cfg: Ling3Config, *, state, n_valid):
    """A chunk at positions start..start+T-1: `ctx` is the MLA layers'
    cached context for positions < start, the KDA layers start from the
    state the lane's last chunk left."""
    B, T = tokens.shape
    positions = start + jnp.broadcast_to(jnp.arange(T), (B, T))
    own = causal_rows(chunk_mask)

    def linear(h, p, i):
        return kda.rows(h[0], p, cfg.kda, state, i, n_valid)[None]

    def attention(h, p, i):
        return mla.attend_cached(h, p, positions, own, ctx, i, cfg)

    return _stack(params, tokens, cfg, linear, attention)


def ling3_decode_kv(params: Params, tokens: jax.Array, positions, ctx,
                    cfg: Ling3Config, *, state):
    """One token a lane: tokens (B,) at `positions`, against the lanes'
    cached context -> (logits (B, Vp) f32, latent rows (MLA layers, B, 1,
    latent_row), rows of no lanes, pairs)."""
    B = tokens.shape[0]
    own = jnp.ones((B, 1, 1), bool)

    def linear(h, p, i):
        return kda.step(h, p, cfg.kda, state, i)

    def attention(h, p, i):
        y, latent = mla.attend_cached(h[:, None], p, positions[:, None],
                                      own, ctx, i, cfg)
        return y[:, 0], latent[:, 0]

    return _stack(params, tokens, cfg, linear, attention)
