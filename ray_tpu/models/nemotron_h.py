"""nemotron_h: a stack of three kinds of block in a given order.

Third model family beside gpt2 and llama, after NVIDIA's Nemotron-H /
Nemotron-3 hybrids (`model_type` nemotron_h). Every block is
``x = x + mixer(rmsnorm(x))`` with ONE mixer, and `layer_pattern`, a
string over three letters, says which:

- ``M``, a Mamba-2 mixer (models/mamba2.py, shared with granite_hybrid):
  ``in_proj`` to z | xBC | dt, a causal depthwise
  convolution over xBC then SiLU, the selective state-space recurrence
  per head ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T``,
  ``y_t = S_t C_t + D x_t`` (B and C shared by the heads of a group), a
  gate ``y * silu(z)`` under a grouped RMSNorm, ``out_proj``;
- ``*``, causal softmax attention with grouped K and V heads, no bias and
  NO rotation of q and k (the family applies no position embedding: the
  Mamba layers carry order);
- ``E``, routed experts (models/moe.py): a sigmoid router with a
  selection bias, the chosen weights normalised and scaled, experts
  ``down(relu(up(h))^2)``, not gated, and one shared expert of the same
  form that every row goes through. `experts_held` and `expert_offset`
  say which of the router's `n_routed_experts` this chip holds: it routes
  over all of them and computes its own experts' part of the result.

The pattern has no clean period, so the stack is a Python loop over the
pattern (each kind written once) and not a scan over uniform blocks; the
parameters are one dict a layer (`params["layers"][i]`), so no layer's
weights are ever sliced out of a stack.

Two kinds of cached state (serve/llm/cache.py): the attention layers' K
and V in pages, `n_kv_layers` of them, and a Mamba layer's recurrent
state a lane slot: the last ``conv_kernel - 1`` conv inputs (a part each) and the
``(heads, head_dim, state)`` SSM state, in float32 as the decay is.
Prompts and chunks run the chunked (SSD) form of the recurrence at
`chunk_size` rows with an initial state; decode runs one step of the
recurrence on every slot where the state lies. Rows past `n_valid`
(bucket padding) get ``dt = 0`` and leave the conv window alone, and a
slot no lane of a decode step owns is written back as read.

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
from ray_tpu.models.mamba2 import ssd_chunked  # noqa: F401 - the family's name for it
from ray_tpu.models.moe import routed_experts
from ray_tpu.ops.context_attention import (
    attend_cached,
    causal_rows,
    softmax_over,
)
from ray_tpu.parallel.sharding import PartitionRules

Params = Any


@dataclasses.dataclass(frozen=True)
class NemotronHConfig:
    """Field names are the published config.json's, but for
    `layer_pattern` (`hybrid_override_pattern`) and the three that say
    what is held here."""

    vocab_size: int = 131072
    hidden_size: int = 2688
    layer_pattern: str = "MEMEM*EME"
    # M
    mamba_num_heads: int = 64
    mamba_head_dim: int = 64
    ssm_state_size: int = 128
    n_groups: int = 8
    conv_kernel: int = 4
    chunk_size: int = 128
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    # *
    num_attention_heads: int = 32
    num_key_value_heads: int = 2
    head_dim: int = 128
    # E
    n_routed_experts: int = 128
    num_experts_per_tok: int = 6
    moe_intermediate_size: int = 1856
    moe_shared_expert_intermediate_size: int = 3712
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    experts_held: int = 128  # of n_routed_experts, from expert_offset on
    expert_offset: int = 0
    layer_norm_epsilon: float = 1e-5
    initializer_range: float = 0.02  # std of a seeded matrix (published)
    max_position_embeddings: int = 262144
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16  # what `init_nemotron_h` creates

    def __post_init__(self):
        if set(self.layer_pattern) - set("ME*"):
            raise ValueError(f"layer_pattern {self.layer_pattern!r}: only "
                             f"M, E and * are layer kinds")
        if self.expert_offset + self.experts_held > self.n_routed_experts:
            raise ValueError("experts held lie outside the router's range")

    # what the engine asks of every family's config
    @property
    def n_layer(self) -> int:
        return len(self.layer_pattern)

    @property
    def block_size(self) -> int:
        return self.max_position_embeddings

    @property
    def padded_vocab(self) -> int:
        return ((self.vocab_size + 127) // 128) * 128

    @property
    def n_kv_layers(self) -> int:
        return self.layer_pattern.count("*")

    @property
    def n_ssm_layers(self) -> int:
        return self.layer_pattern.count("M")

    @property
    def d_inner(self) -> int:
        return self.mamba_num_heads * self.mamba_head_dim

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.n_groups * self.ssm_state_size

    @property
    def mamba(self) -> mamba2.Mamba2Sizes:
        """What the shared mixer (models/mamba2.py) asks of a family."""
        return mamba2.Mamba2Sizes(
            heads=self.mamba_num_heads, head_dim=self.mamba_head_dim,
            state=self.ssm_state_size, groups=self.n_groups,
            conv_kernel=self.conv_kernel, chunk=self.chunk_size,
            eps=self.layer_norm_epsilon, dtype=self.dtype)

    def state_parts(self) -> tuple:
        """(name, shape a lane and layer, dtype) of a Mamba layer's
        recurrent state, for `cache.StateLayout`: the conv window a part
        a row and the SSM state (`Mamba2Sizes.state_parts`)."""
        return self.mamba.state_parts()

    @staticmethod
    def tiny() -> "NemotronHConfig":
        """Every layer kind at a size for CPU tests, float32: 16 experts
        of which 8 (from the 4th on) are held, chunks of 8 rows."""
        return NemotronHConfig(
            vocab_size=512, hidden_size=64, layer_pattern="MEM*EME",
            mamba_num_heads=8, mamba_head_dim=8, ssm_state_size=16,
            n_groups=2, chunk_size=8, num_attention_heads=4,
            num_key_value_heads=2, head_dim=16, n_routed_experts=16,
            num_experts_per_tok=3, moe_intermediate_size=32,
            moe_shared_expert_intermediate_size=48, experts_held=8,
            expert_offset=4, max_position_embeddings=256,
            dtype=jnp.float32, param_dtype=jnp.float32)

    @staticmethod
    def nano_30b_a3b() -> "NemotronHConfig":
        """NVIDIA-Nemotron-3-Nano-30B-A3B-BF16 as published
        (huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16,
        config.json): 52 blocks of 2688, every expert held (63 GB in
        bf16: the base of the cut below, served nowhere here)."""
        return NemotronHConfig(
            layer_pattern="MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*"
                          "EMEMEMEME")

    @staticmethod
    def nano_30b_a3b_l18_ep4() -> "NemotronHConfig":
        """One chip's share where the four chips of a v5e host share each
        layer: the first 18 of 52 blocks (8 M, 8 E, 2 *), 32 of the 128
        experts and 32,768 of the 131,072 vocabulary rows; every width
        as published (PERF.md section 4)."""
        full = NemotronHConfig.nano_30b_a3b()
        return dataclasses.replace(
            full, layer_pattern=full.layer_pattern[:18], experts_held=32,
            vocab_size=32768, max_position_embeddings=2560)


def nemotron_h_partition_rules() -> PartitionRules:
    """The held experts over `expert`; the vocabulary over `tensor`;
    everything else (mixers, router, shared expert) whole on every
    device, as the stated deployment has it."""
    from jax.sharding import PartitionSpec as P

    return PartitionRules([
        (r"layers/\d+/(we_up|we_down)$", P("expert", None, None)),
        (r"wte$", P("tensor", None)),
        (r"lm_head$", P(None, "tensor")),
        (r".*", P()),
    ])


@functools.partial(jax.jit, static_argnames=("cfg",))
def init_nemotron_h(key: jax.Array, cfg: NemotronHConfig) -> Params:
    """One program for the whole tree, every leaf drawn in float32 and
    written in `cfg.param_dtype` by the same fusion. Matrices are normal
    with std `initializer_range`, those that write the residual stream
    that over sqrt(L)
    (`rescale_prenorm_residual`); norm scales 1. The state-space
    parameters follow the family's rule: `A_log` the log of uniform
    1..16, `dt_bias` the inverse softplus of a dt log-uniform in
    `time_step_min`..`time_step_max` and floored at `time_step_floor`,
    `D` 1, the conv as torch's Conv1d default (uniform within
    1 / sqrt(conv_kernel)). The router's selection bias is small noise
    (std 0.02, a sixth of the scores' spread at matrices of std 0.01: more
    would send most rows to the same few experts), so that choosing (with
    it) and weighting (without) differ."""
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

    def mamba(k):
        ks = jax.random.split(k, 6)
        H, C, K = cfg.mamba_num_heads, cfg.conv_dim, cfg.conv_kernel
        bound = 1.0 / math.sqrt(K)
        dt = jnp.exp(jax.random.uniform(ks[2], (H,), jnp.float32)
                     * (math.log(cfg.time_step_max)
                        - math.log(cfg.time_step_min))
                     + math.log(cfg.time_step_min))
        dt = jnp.maximum(dt, cfg.time_step_floor)
        return {
            "norm": jnp.ones((D,), pdt),
            "in_proj": normal(ks[0], (D, cfg.d_inner + C + H), std),
            "conv_w": jax.random.uniform(
                ks[1], (K, C), jnp.float32, -bound, bound).astype(pdt),
            "conv_b": jax.random.uniform(
                ks[5], (C,), jnp.float32, -bound, bound).astype(pdt),
            "dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(pdt),
            "A_log": jnp.log(jax.random.uniform(
                ks[3], (H,), jnp.float32, 1.0, 16.0)).astype(pdt),
            "D": jnp.ones((H,), pdt),
            "gate_norm": jnp.ones((cfg.d_inner,), pdt),
            "out_proj": normal(ks[4], (cfg.d_inner, D), out_std),
        }

    def attention(k):
        ks = jax.random.split(k, 4)
        q_dim = cfg.num_attention_heads * cfg.head_dim
        kv_dim = cfg.num_key_value_heads * cfg.head_dim
        return {
            "norm": jnp.ones((D,), pdt),
            "wq": normal(ks[0], (D, q_dim), std),
            "wk": normal(ks[1], (D, kv_dim), std),
            "wv": normal(ks[2], (D, kv_dim), std),
            "wo": normal(ks[3], (q_dim, D), out_std),
        }

    def routed(k):
        ks = jax.random.split(k, 6)
        X, F = cfg.experts_held, cfg.moe_intermediate_size
        Fs = cfg.moe_shared_expert_intermediate_size
        return {
            "norm": jnp.ones((D,), pdt),
            "router": normal(ks[0], (D, cfg.n_routed_experts), std),
            "router_bias": normal(ks[1], (cfg.n_routed_experts,), 0.02),
            "we_up": experts(ks[2], (X, D, F), std),
            "we_down": experts(ks[3], (X, F, D), out_std),
            "ws_up": normal(ks[4], (D, Fs), std),
            "ws_down": normal(ks[5], (Fs, D), out_std),
        }

    make = {"M": mamba, "*": attention, "E": routed}
    layers = [make[kind](k) for kind, k in zip(
        cfg.layer_pattern, jax.random.split(k_layers, L))]
    return {"wte": normal(k_wte, (V, D), std), "layers": layers,
            "lnf": jnp.ones((D,), pdt),
            "lm_head": normal(k_head, (D, V), std)}


# --------------------------------------------------------------------------
# the three mixers, each written once (the Mamba-2 one in models/mamba2.py)


def _rmsnorm(x, scale, eps):
    x32 = x.astype(jnp.float32)
    rms = jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (x32 * rms * scale.astype(jnp.float32)).astype(x.dtype)


def _mamba_rows(h, p, cfg: NemotronHConfig, view, index: int, n_valid):
    """The Mamba mixer (models/mamba2.py) on one lane's normed rows h (T,
    D), from the state in the lane's slot and leaving the state after row
    ``n_valid - 1`` there."""
    return mamba2.rows(h, p, cfg.mamba, view, index, n_valid)


def _mamba_step(h, p, cfg: NemotronHConfig, view, index: int):
    """One step of the recurrence for a decode batch h (Sb, D), every
    slot of the layer updated where it lies."""
    return mamba2.step(h, p, cfg.mamba, view, index)


def _qkv(h, p, cfg: NemotronHConfig):
    """Normed rows h (..., D) -> q (..., HK, R, hd), k and v (..., HK,
    hd): the R query heads of a group side by side, not rotated."""
    dt = cfg.dtype
    HK, hd = cfg.num_key_value_heads, cfg.head_dim
    R = cfg.num_attention_heads // HK
    lead = h.shape[:-1]
    return ((h @ p["wq"].astype(dt)).reshape(*lead, HK, R, hd),
            (h @ p["wk"].astype(dt)).reshape(*lead, HK, hd),
            (h @ p["wv"].astype(dt)).reshape(*lead, HK, hd))


def _attend(q, segments, p, cfg: NemotronHConfig):
    """q (B, T, HK, R, hd) against the rows of every segment ``(keys,
    values (B, S, HK, hd), valid (B, T, S))`` under one softmax in
    float32 -> (B, T, D) after `wo`. K and V are never repeated R times:
    the heads of a group share them in the product."""
    B, T = q.shape[:2]
    att = softmax_over(q, segments, 1.0 / math.sqrt(cfg.head_dim), cfg.dtype)
    return att.reshape(B, T, -1) @ p["wo"].astype(cfg.dtype)


def _attend_cached(q, k, v, own_valid, ctx, index: int, p,
                   cfg: NemotronHConfig):
    """As `_attend`, of the program's own rows k, v (B, T, HK, hd) where
    `own_valid` (B, T, T) allows and the lanes' cached context ``ctx`` of
    attention layer `index`, read in tiles and to the lane's length
    (ops/context_attention.py)."""
    B, T = q.shape[:2]
    att = attend_cached(q, k, v, own_valid, ctx, index, cfg.dtype)
    return att.reshape(B, T, -1) @ p["wo"].astype(cfg.dtype)


def _experts(h, p, cfg: NemotronHConfig):
    """Normed rows h (N, D) -> (the held experts' part of the routed sum
    plus the shared expert, pairs per expert over ALL experts)."""
    dt = cfg.dtype
    up, down = p["we_up"].astype(dt), p["we_down"].astype(dt)

    def shared(rows):
        a = jax.nn.relu(rows @ p["ws_up"].astype(dt))
        return (a * a) @ p["ws_down"].astype(dt)

    def expert_fn(rows, mm):
        a = jax.nn.relu(mm(rows, up))
        return mm(a * a, down)

    y, counts, _ = routed_experts(
        h, p["router"], expert_fn, k=cfg.num_experts_per_tok,
        norm_topk=cfg.norm_topk_prob, score="sigmoid",
        select_bias=p["router_bias"], scale=cfg.routed_scaling_factor,
        held=(cfg.expert_offset, cfg.experts_held), shared=shared)
    return y, counts


def _stack(params, x, cfg: NemotronHConfig, mamba, attention):
    """The blocks in the pattern's order on x (B, T, D) or (B, D).
    ``mamba(h, p, i)`` and ``attention(h, p, i) -> (out, k, v)`` are the
    program's way through those two kinds, `i` counting the layers of
    that kind; the expert kind is the same in every program. Returns
    (logits f32, k, v stacked over the attention layers, pairs per
    expert stacked over the expert layers)."""
    eps = cfg.layer_norm_epsilon
    seen = {"M": 0, "*": 0}
    ks, vs, counts = [], [], []
    for kind, p in zip(cfg.layer_pattern, params["layers"]):
        h = _rmsnorm(x, p["norm"], eps)
        if kind == "M":
            y = mamba(h, p, seen["M"])
            seen["M"] += 1
        elif kind == "*":
            y, k, v = attention(h, p, seen["*"])
            seen["*"] += 1
            ks.append(k)
            vs.append(v)
        else:
            y, c = _experts(h.reshape(-1, h.shape[-1]), p, cfg)
            y = y.reshape(h.shape)
            counts.append(c)
        x = x + y
    x = _rmsnorm(x, params["lnf"], eps)
    logits = (x @ params["lm_head"].astype(cfg.dtype)).astype(jnp.float32)
    return logits, jnp.stack(ks), jnp.stack(vs), jnp.stack(counts)


# --------------------------------------------------------------------------
# KV-cache and state inference steps (serve.llm): the models own the
# mathematics, serve/llm/runner.py the pages, `state` (a cache.StateView)
# the recurrent state's reads and writes.


def nemotron_h_prefill_kv(params: Params, tokens: jax.Array,
                          cfg: NemotronHConfig, *, state, n_valid):
    """A whole prompt from position 0: tokens (1, T), of which the first
    `n_valid` are real -> (logits (1, T, Vp) f32, k, v (n_kv_layers, 1,
    T, HK, hd), pairs (n_expert_layers, n_routed_experts))."""
    T = tokens.shape[1]
    valid = jnp.tril(jnp.ones((T, T), bool))[None]

    def mamba(h, p, i):
        return _mamba_rows(h[0], p, cfg, state, i, n_valid)[None]

    def attention(h, p, i):
        q, k, v = _qkv(h, p, cfg)
        return _attend(q, [(k, v, valid)], p, cfg), k, v

    x = params["wte"].astype(cfg.dtype)[tokens]
    return _stack(params, x, cfg, mamba, attention)


def nemotron_h_prefill_chunk_kv(params: Params, tokens: jax.Array, start,
                                ctx, chunk_mask, cfg: NemotronHConfig, *,
                                state, n_valid):
    """A chunk at positions start..start+T-1: ``ctx`` holds the attention
    layers' cached context (rows (HK, hd)) for positions < start, the
    Mamba layers start from the state the lane's last chunk left."""
    own = causal_rows(chunk_mask)

    def mamba(h, p, i):
        return _mamba_rows(h[0], p, cfg, state, i, n_valid)[None]

    def attention(h, p, i):
        q, k, v = _qkv(h, p, cfg)
        return _attend_cached(q, k, v, own, ctx, i, p, cfg), k, v

    x = params["wte"].astype(cfg.dtype)[tokens]
    return _stack(params, x, cfg, mamba, attention)


def nemotron_h_decode_kv(params: Params, tokens: jax.Array, positions,
                         ctx, cfg: NemotronHConfig, *, state):
    """One token a lane: tokens (B,), against the lanes' cached context
    ``ctx`` -> (logits (B, Vp) f32, k_new, v_new (n_kv_layers, B, HK,
    hd), pairs)."""
    B = tokens.shape[0]
    own = jnp.ones((B, 1, 1), bool)

    def mamba(h, p, i):
        return _mamba_step(h, p, cfg, state, i)

    def attention(h, p, i):
        q, k, v = _qkv(h, p, cfg)
        out = _attend_cached(q[:, None], k[:, None], v[:, None], own, ctx,
                             i, p, cfg)
        return out[:, 0], k, v

    x = params["wte"].astype(cfg.dtype)[tokens]
    return _stack(params, x, cfg, mamba, attention)
