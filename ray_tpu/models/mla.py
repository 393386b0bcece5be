"""Latent attention (MLA) and the feed-forwards that go with it, each part
written once: what the glm_dsa family (models/glm_dsa.py: MLA under a
learned indexer), the xing4 family (models/xing4.py: MLA that reads
every cached slot, YaRN frequencies) and the ling3 family
(models/ling3.py: one such layer to five linear-attention layers, no
low-rank query) run. All are DeepSeek-V3's layer; what differs between
them (the indexer; the residual path; the other mixer) stays in the
family's own module.

- **queries**: ``c_q = rmsnorm(h W_qa)`` (`q_lora_rank`), ``q = c_q W_qb``
  (`q_lora_rank` None: ``q = h W_q``, no latent and no norm)
  -> `num_attention_heads` heads of ``qk_nope_head_dim | qk_rope_head_dim``;
  the second part is rotated, **interleaved** pairs ``(2i, 2i + 1)``, at
  the family's frequencies (`cfg.rotate`: `rope` with `plain_frequencies`
  or `yarn_frequencies`);
- **the latent row** ``[rmsnorm(c_kv) | k_pe rotated | zeros]`` from ``h
  W_kva``: all that is cached of a token and layer;
- **absorbed**: ``q_nope W_kvb[k]^T`` is a `kv_lora_rank`-wide query on
  `c_kv`, the value is `c_kv`, and ``W_kvb[v]`` is applied after the
  softmax (`absorbed_query`, `values_out`); **up-projected**: a K and a V
  head from every latent row (`up_project`). The two are equal in exact
  arithmetic;
- scores are scaled by `cfg.softmax_scale` (``1 / sqrt(qk_head_dim)``,
  times YaRN's ``mscale^2`` where the family has one);
- the two ways through a layer that reads EVERY cached slot (no indexer):
  a whole prompt's own rows up-projected (`attend_rows`), a chunk's and a
  decode step's rows absorbed against the cached latent rows and their
  own (`attend_cached`);
- the **dense** SwiGLU feed-forward, and the **routed experts**
  (models/moe.py): a sigmoid router with a selection bias (`noaux_tc`; one
  group, or the `topk_group` best of `n_group`), weights normalised and
  times `routed_scaling_factor`, a shared expert on every row;
  `experts_held` and `expert_offset` say which of the router's experts
  this chip holds.

A family's config brings the published field names (`num_attention_heads`,
`q_lora_rank`, `kv_lora_rank`, `qk_nope_head_dim`, `qk_rope_head_dim`,
`v_head_dim`, `latent_pad`, `rms_norm_eps`, `dtype`, the router's) and
`rotate(x, positions)`, `softmax_scale`. Matrix products are in `dtype`;
norms, rotation, softmax and router are float32. Nothing is built when
this module is imported.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.models.moe import routed_experts
from ray_tpu.ops.context_attention import attend_latent, softmax_over


def rmsnorm(x, scale, eps):
    x32 = x.astype(jnp.float32)
    rms = jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (x32 * rms * scale.astype(jnp.float32)).astype(x.dtype)


def plain_frequencies(theta: float, width: int):
    """``theta^(-2i / width)`` for the pairs i of `width` rotated lanes."""
    half = width // 2
    return theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)


def yarn_frequencies(theta: float, width: int, *, factor: float,
                     original: int, beta_fast: float, beta_slow: float):
    """YaRN's blend (arXiv:2309.00071, as `transformers`
    `_compute_yarn_parameters` has it): a pair that turns more than
    `beta_fast` times over the `original` context keeps its frequency, one
    that turns less than `beta_slow` times has it divided by `factor`, the
    pairs between (whole pair indices: floor and ceiling) a linear ramp.
    A constant of the program, computed in float64."""
    half = width // 2
    plain = theta ** (-np.arange(half, dtype=np.float64) / half)

    def pair_turning(turns):
        return width * math.log(original / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(pair_turning(beta_fast)), 0)
    high = min(math.ceil(pair_turning(beta_slow)), width - 1)
    if low == high:
        high += 0.001
    keep = 1.0 - np.clip((np.arange(half) - low) / (high - low), 0.0, 1.0)
    return jnp.asarray(plain / factor * (1.0 - keep) + plain * keep,
                       jnp.float32)


def yarn_mscale(factor: float, mscale: float) -> float:
    """``0.1 mscale ln(factor) + 1``: its square scales the softmax."""
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def rope(x, positions, freqs, width: int):
    """The first `width` lanes of x (*positions.shape, [heads,] D) rotated
    by `positions` at `freqs` (width / 2,), interleaved pairs ``(2i, 2i +
    1)``; the other lanes as they are."""
    half = width // 2
    angles = positions.astype(jnp.float32)[..., None] * freqs
    if x.ndim == positions.ndim + 2:  # a heads dimension
        angles = angles[..., None, :]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    pairs = x[..., :width].astype(jnp.float32).reshape(
        *x.shape[:-1], half, 2)
    x1, x2 = pairs[..., 0], pairs[..., 1]
    turned = jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return jnp.concatenate(
        [turned.reshape(*x.shape[:-1], width).astype(x.dtype),
         x[..., width:]], axis=-1)


def queries(h, p, positions, cfg):
    """Normed rows h (..., D) -> (q_nope (..., H, nope), q_pe (..., H,
    rope) rotated, the normed query latent c_q (..., q_lora_rank); None
    where the family has no low-rank pair)."""
    dt = cfg.dtype
    with jax.named_scope("attn.mla.q"):
        if cfg.q_lora_rank is None:
            c_q, q = None, h @ p["wq"].astype(dt)
        else:
            c_q = rmsnorm(h @ p["wq_a"].astype(dt), p["q_norm"],
                          cfg.rms_norm_eps)
            q = c_q @ p["wq_b"].astype(dt)
        q = q.reshape(
            *h.shape[:-1], cfg.num_attention_heads, cfg.qk_head_dim)
        q_pe = cfg.rotate(q[..., cfg.qk_nope_head_dim:], positions)
    return q[..., :cfg.qk_nope_head_dim], q_pe, c_q


def latent(h, p, positions, cfg):
    """Normed rows h (..., D) -> their latent rows (..., latent_row):
    ``[rmsnorm(c_kv) | k_pe rotated | zeros]``, what the pool holds."""
    R = cfg.kv_lora_rank
    with jax.named_scope("attn.mla.kv"):
        ckv = h @ p["wkv_a"].astype(cfg.dtype)
        return jnp.concatenate(
            [rmsnorm(ckv[..., :R], p["kv_norm"], cfg.rms_norm_eps),
             cfg.rotate(ckv[..., R:], positions),
             jnp.zeros(ckv.shape[:-1] + (cfg.latent_pad,), ckv.dtype)],
            axis=-1)


def absorbed_query(q_nope, q_pe, p, cfg):
    """The heads' queries on a latent row (B, T, H, latent_row):
    ``[q_nope W_kvb[k]^T | q_pe | zeros]``."""
    dt = cfg.dtype
    with jax.named_scope("attn.mla.q"):
        return jnp.concatenate(
            [jnp.einsum("bthd,rhd->bthr", q_nope, p["wk_b"].astype(dt)),
             q_pe, jnp.zeros(q_pe.shape[:-1] + (cfg.latent_pad,), dt)],
            axis=-1)


def up_project(rows, q_pe, p, cfg):
    """Latent rows (B, S, latent_row) -> a K head (B, S, H, qk_head_dim:
    ``[c_kv W_kvb[k] | k_pe]``, the rotated part one for all heads) and a
    V head (B, S, H, v_head_dim) of each. `q_pe` gives the heads' shape."""
    dt = cfg.dtype
    c_kv = rows[..., :cfg.kv_lora_rank]
    k_pe = rows[..., cfg.kv_lora_rank:][..., :cfg.qk_rope_head_dim]
    k_nope = jnp.einsum("bsr,rhd->bshd", c_kv, p["wk_b"].astype(dt))
    v = jnp.einsum("bsr,rhd->bshd", c_kv, p["wv_b"].astype(dt))
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_pe[:, :, None], q_pe.shape)], -1)
    return k, v


def output(att, p, cfg):
    """att (B, T, H, vd) -> (B, T, D)."""
    with jax.named_scope("attn.mla.out"):
        B, T = att.shape[:2]
        return att.astype(cfg.dtype).reshape(B, T, -1) \
            @ p["wo"].astype(cfg.dtype)


def values_out(att, p, cfg):
    """An absorbed softmax's result att (B, T, H, kv_lora_rank), weights
    on `c_kv`, -> (B, T, D): ``W_kvb[v]`` a head, then the output."""
    with jax.named_scope("attn.mla.out"):
        att = jnp.einsum("bthr,rhd->bthd", att,
                         p["wv_b"].astype(cfg.dtype))
    return output(att, p, cfg)


def attend_rows(h, p, positions, seen, cfg):
    """A whole prompt's own rows h (B, T, D), nothing cached: the latent
    rows up-projected to a K and a V head each, `seen` (B, T, T) the
    softmax's mask. -> (out (B, T, D), latent rows)."""
    q_nope, q_pe, _ = queries(h, p, positions, cfg)
    rows = latent(h, p, positions, cfg)
    with jax.named_scope("attn.mla.core"):
        k, v = up_project(rows, q_pe, p, cfg)
        q = jnp.concatenate([q_nope, q_pe], axis=-1)
        att = softmax_over(q[:, :, :, None], [(k, v, seen)],
                           cfg.softmax_scale, cfg.dtype)[:, :, :, 0]
    return output(att, p, cfg), rows


def attend_cached(h, p, positions, own_valid, ctx, layer, cfg):
    """Rows h (B, T, D) of a chunk or a decode step against every cached
    latent row of their lanes and their own, absorbed: every head's query
    on the one latent row, ``W_kvb[v]`` after the softmax. -> (out (B, T,
    D), latent rows)."""
    q_nope, q_pe, _ = queries(h, p, positions, cfg)
    rows = latent(h, p, positions, cfg)
    q = absorbed_query(q_nope, q_pe, p, cfg)
    with jax.named_scope("attn.mla.dense"):
        att = attend_latent(q, rows, own_valid, ctx, layer, cfg.dtype,
                            values=cfg.kv_lora_rank,
                            scale=cfg.softmax_scale)
    return values_out(att, p, cfg), rows


def swiglu(h, gate, up, down, dt):
    return (jax.nn.silu(h @ gate.astype(dt)) * (h @ up.astype(dt))) \
        @ down.astype(dt)


def dense(h, p, cfg):
    with jax.named_scope("ffn.dense"):
        return swiglu(h, p["w_gate"], p["w_up"], p["w_down"], cfg.dtype)


def experts(h, p, cfg):
    """Normed rows h (N, D) -> (the held experts' part of the routed sum
    plus the shared expert, pairs per expert over ALL experts)."""
    dt = cfg.dtype
    wg, wu, wd = (p[n].astype(dt) for n in ("we_gate", "we_up", "we_down"))
    y, counts, _ = routed_experts(
        h, p["router"],
        lambda a, mm: mm(jax.nn.silu(mm(a, wg)) * mm(a, wu), wd),
        k=cfg.num_experts_per_tok, norm_topk=cfg.norm_topk_prob,
        score="sigmoid", select_bias=p["router_bias"],
        scale=cfg.routed_scaling_factor,
        held=(cfg.expert_offset, cfg.experts_held),
        shared=lambda a: swiglu(a, p["ws_gate"], p["ws_up"], p["ws_down"],
                                dt),
        # a family whose config has no group fields routes without a limit
        n_group=getattr(cfg, "n_group", 1),
        topk_group=getattr(cfg, "topk_group", 1))
    return y, counts
