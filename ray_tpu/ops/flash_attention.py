"""Blockwise causal flash attention — pallas TPU kernels, fwd + bwd.

This is the fused-attention role the reference delegates to
cuDNN/torch SDPA (SURVEY.md §2.5); on TPU we own the kernel. Design
(FlashAttention-2 style, online softmax):

- forward: grid (B*H, T/Bq, T/Bk), innermost k-blocks sequential; scratch
  carries the running row-max m, row-sum l and the f32 output accumulator
  across k-blocks; softmax statistics are float32 always; the logsumexp
  per row is emitted for the backward pass.
- backward: two kernels (no atomics on TPU) — dq over (BH, q, k) and
  dk/dv over (BH, k, q) — both recompute p = exp(s - lse) blockwise, so
  nothing O(T²) is ever materialized.
- causal blocks strictly above the diagonal are skipped entirely
  (`pl.when` on block indices), halving compute at long T.
- matmuls run on the MXU with preferred_element_type=float32; inputs may
  be bfloat16.

All kernels run in interpret mode on CPU for testing.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_MASK_VALUE = -0.7 * float(jnp.finfo(jnp.float32).max)


@dataclasses.dataclass(frozen=True)
class _Cfg:
    causal: bool
    sm_scale: float
    block_q: int
    block_k: int
    interpret: bool


_vmem_spec = functools.partial(pl.BlockSpec, memory_space=pltpu.VMEM)


# ---------------------------------------------------------------- forward


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc, m_s, l_s, *, cfg,
                nk):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    Bq = q_ref.shape[1]
    Bk = k_ref.shape[1]

    @pl.when(ki == 0)
    def _init():
        m_s[:] = jnp.full_like(m_s, -jnp.inf)
        l_s[:] = jnp.zeros_like(l_s)
        acc[:] = jnp.zeros_like(acc)

    run = True
    if cfg.causal:
        run = ki * Bk <= qi * Bq + Bq - 1

    @pl.when(run)
    def _step():
        q = q_ref[0]  # (Bq, D)
        k = k_ref[0]  # (Bk, D)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * cfg.sm_scale
        if cfg.causal:
            rows = qi * Bq + jax.lax.broadcasted_iota(jnp.int32, (Bq, Bk), 0)
            cols = ki * Bk + jax.lax.broadcasted_iota(jnp.int32, (Bq, Bk), 1)
            s = jnp.where(cols <= rows, s, DEFAULT_MASK_VALUE)
        m_prev = m_s[:, :1]  # (Bq, 1)
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)  # (Bq, 1)
        p = jnp.exp(s - m_new)  # (Bq, Bk) f32
        l_new = alpha * l_s[:, :1] + jnp.sum(p, axis=-1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        acc[:] = acc[:] * alpha + pv
        m_s[:] = jnp.broadcast_to(m_new, m_s.shape)
        l_s[:] = jnp.broadcast_to(l_new, l_s.shape)

    @pl.when(ki == nk - 1)
    def _emit():
        l = l_s[:, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc[:] / l_safe).astype(o_ref.dtype)
        lse_ref[0] = m_s[:, :1] + jnp.log(l_safe)


def _fwd(q, k, v, cfg: _Cfg):
    BH, T, D = q.shape
    nq = T // cfg.block_q
    nk = T // cfg.block_k
    Bq, Bk = cfg.block_q, cfg.block_k
    kernel = functools.partial(_fwd_kernel, cfg=cfg, nk=nk)
    scratch = [
        pltpu.VMEM((Bq, D), jnp.float32),
        pltpu.VMEM((Bq, 128), jnp.float32),
        pltpu.VMEM((Bq, 128), jnp.float32),
    ]
    o, lse = pl.pallas_call(
        kernel,
        grid=(BH, nq, nk),
        in_specs=[
            _vmem_spec((1, Bq, D), lambda b, i, j: (b, i, 0)),
            _vmem_spec((1, Bk, D), lambda b, i, j: (b, j, 0)),
            _vmem_spec((1, Bk, D), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=[
            _vmem_spec((1, Bq, D), lambda b, i, j: (b, i, 0)),
            _vmem_spec((1, Bq, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, T, D), q.dtype),
            jax.ShapeDtypeStruct((BH, T, 1), jnp.float32),
        ],
        scratch_shapes=scratch,
        interpret=cfg.interpret,
    )(q, k, v)
    return o, lse


# ---------------------------------------------------------------- backward


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               dq_acc, *, cfg, nk):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    Bq = q_ref.shape[1]
    Bk = k_ref.shape[1]

    @pl.when(ki == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    run = True
    if cfg.causal:
        run = ki * Bk <= qi * Bq + Bq - 1

    @pl.when(run)
    def _step():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * cfg.sm_scale
        p = jnp.exp(s - lse_ref[0])  # (Bq, Bk); lse block is (Bq, 1)
        if cfg.causal:
            rows = qi * Bq + jax.lax.broadcasted_iota(jnp.int32, (Bq, Bk), 0)
            cols = ki * Bk + jax.lax.broadcasted_iota(jnp.int32, (Bq, Bk), 1)
            p = jnp.where(cols <= rows, p, 0.0)
        dp = jax.lax.dot_general(
            do_ref[0], v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0]) * cfg.sm_scale
        dq_acc[:] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _emit():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref,
                dv_ref, dk_acc, dv_acc, *, cfg, nq):
    ki = pl.program_id(1)
    qi = pl.program_id(2)
    Bk = k_ref.shape[1]
    Bq = q_ref.shape[1]

    @pl.when(qi == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    run = True
    if cfg.causal:
        run = ki * Bk <= qi * Bq + Bq - 1

    @pl.when(run)
    def _step():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * cfg.sm_scale
        p = jnp.exp(s - lse_ref[0])
        if cfg.causal:
            rows = qi * Bq + jax.lax.broadcasted_iota(jnp.int32, (Bq, Bk), 0)
            cols = ki * Bk + jax.lax.broadcasted_iota(jnp.int32, (Bq, Bk), 1)
            p = jnp.where(cols <= rows, p, 0.0)
        dv_acc[:] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0]) * cfg.sm_scale
        dk_acc[:] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(qi == nq - 1)
    def _emit():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _bwd(q, k, v, o, lse, do, cfg: _Cfg):
    BH, T, D = q.shape
    Bq, Bk = cfg.block_q, cfg.block_k
    nq, nk = T // Bq, T // Bk
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1, keepdims=True)  # (BH, T, 1)

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, cfg=cfg, nk=nk),
        grid=(BH, nq, nk),
        in_specs=[
            _vmem_spec((1, Bq, D), lambda b, i, j: (b, i, 0)),
            _vmem_spec((1, Bk, D), lambda b, i, j: (b, j, 0)),
            _vmem_spec((1, Bk, D), lambda b, i, j: (b, j, 0)),
            _vmem_spec((1, Bq, D), lambda b, i, j: (b, i, 0)),
            _vmem_spec((1, Bq, 1), lambda b, i, j: (b, i, 0)),
            _vmem_spec((1, Bq, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_specs=_vmem_spec((1, Bq, D), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, T, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((Bq, D), jnp.float32)],
        interpret=cfg.interpret,
    )(q, k, v, do, lse, delta)

    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, cfg=cfg, nq=nq),
        grid=(BH, nk, nq),
        in_specs=[
            _vmem_spec((1, Bq, D), lambda b, j, i: (b, i, 0)),
            _vmem_spec((1, Bk, D), lambda b, j, i: (b, j, 0)),
            _vmem_spec((1, Bk, D), lambda b, j, i: (b, j, 0)),
            _vmem_spec((1, Bq, D), lambda b, j, i: (b, i, 0)),
            _vmem_spec((1, Bq, 1), lambda b, j, i: (b, i, 0)),
            _vmem_spec((1, Bq, 1), lambda b, j, i: (b, i, 0)),
        ],
        out_specs=[
            _vmem_spec((1, Bk, D), lambda b, j, i: (b, j, 0)),
            _vmem_spec((1, Bk, D), lambda b, j, i: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, T, D), k.dtype),
            jax.ShapeDtypeStruct((BH, T, D), v.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((Bk, D), jnp.float32),
                        pltpu.VMEM((Bk, D), jnp.float32)],
        interpret=cfg.interpret,
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


# ---------------------------------------------------------------- public


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _flash(q, k, v, cfg: _Cfg):
    o, _ = _fwd(q, k, v, cfg)
    return o


def _flash_fwd(q, k, v, cfg: _Cfg):
    o, lse = _fwd(q, k, v, cfg)
    # Name the kernel outputs so a remat policy can SAVE them: under
    # jax.checkpoint(block) the backward replay would otherwise re-run
    # this pallas forward just to rebuild (o, lse) residuals — the
    # lse-saving policy (models.gpt2 remat_policy="save_flash") keeps
    # them and the replay's flash fwd is dead-code-eliminated.
    from jax.ad_checkpoint import checkpoint_name

    o_res = checkpoint_name(o, "flash_o")
    lse = checkpoint_name(lse, "flash_lse")
    return o, (q, k, v, o_res, lse)


def _flash_bwd(cfg: _Cfg, res, do):
    q, k, v, o, lse = res
    return _bwd(q, k, v, o, lse, do, cfg)


_flash.defvjp(_flash_fwd, _flash_bwd)


def _fit_block(T: int, want: int) -> int:
    """Largest power-of-two block <= want that divides T (so e.g. T=1536
    runs with 512 blocks instead of failing the 1024 default)."""
    b = min(want, T)
    while b > 128 and T % b:
        b //= 2
    return b


def flash_attention(q, k, v, *, causal: bool = True,
                    sm_scale: float | None = None,
                    block_q: int = 1024, block_k: int = 1024,
                    interpret: bool = False) -> jax.Array:
    """q,k,v: (B, T, H, D) -> (B, T, H, D).

    Differentiable (custom VJP with flash backward kernels). Requires T
    divisible by the block sizes (the dispatcher in ops.attention falls
    back to the einsum path otherwise)."""
    B, T, H, D = q.shape
    block_q = _fit_block(T, block_q)
    block_k = _fit_block(T, block_k)
    if T % block_q or T % block_k:
        raise ValueError(f"T={T} not divisible by blocks "
                         f"({block_q},{block_k})")
    if sm_scale is None:
        sm_scale = 1.0 / (D ** 0.5)
    cfg = _Cfg(causal=causal, sm_scale=float(sm_scale),
               block_q=block_q, block_k=block_k, interpret=interpret)

    def to_bh(t):  # (B,T,H,D) -> (B*H, T, D)
        return t.transpose(0, 2, 1, 3).reshape(B * H, T, D)

    o = _flash(to_bh(q), to_bh(k), to_bh(v), cfg)
    return o.reshape(B, H, T, D).transpose(0, 2, 1, 3)
