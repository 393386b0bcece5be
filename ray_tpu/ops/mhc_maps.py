"""The coefficient maps of a hyper-connected half-layer, one kernel: from
the logits ``u`` (n^2 + 2n, N), tokens last, to ``H_pre = sigmoid(.)``,
``H_post = 2 sigmoid(.)`` and ``H_res``, the Sinkhorn-Knopp projection of
``exp(clip(.))`` (models/xing4.py has the equations).

Why a kernel: a Sinkhorn iteration is two normalisations of a 4 x 4 a
token, each a sum that every entry is then divided by, so every
intermediate has two consumers and XLA:TPU, which will not duplicate a
division into both, leaves each as a fusion of its own: some 80 operations
of a microsecond or two a half-layer, a thousand a program at 12
half-layers (the AOT compile for the v5e, PERF.md section 6, PR 51). Here
the n^2 entries are n^2 row vectors over a block of tokens in vector
registers, all `iters` iterations unrolled, and nothing but ``u`` and the
coefficients touches memory. Every iteration is run.

The jnp form (`maps_reference`) is the path of the CPU and the kernel's
oracle; `runs_as_kernel` is the one place that picks.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK_TOKENS = 512  # tokens a block where a program has more


def runs_as_kernel() -> bool:
    """On a TPU, on one chip (no mesh partitions a Pallas call)."""
    return (jax.default_backend() == "tpu"
            and jax.sharding.get_abstract_mesh().size <= 1)  # 0: no mesh


def _sum_over(M, axis: int):
    parts = [jax.lax.index_in_dim(M, i, axis, keepdims=True)
             for i in range(M.shape[axis])]
    return functools.reduce(jnp.add, parts)


def sinkhorn(M, iters: int, eps: float):
    """M (n, n, N) positive -> `iters` times: every column divided by its
    sum plus `eps`, then every row: doubly stochastic in the limit, rows
    summing to one (within `eps`) after any iteration."""
    for _ in range(iters):
        M = M / (_sum_over(M, 0) + eps)
        M = M / (_sum_over(M, 1) + eps)
    return M


def maps_reference(z, *, n: int, iters: int, eps: float, lo: float,
                   hi: float):
    """z (n^2 + 2n, N) float32, the maps' logits with scale and bias
    applied -> the coefficients, same shape: rows [0, n) H_pre, [n, 2n)
    H_post, the rest H_res row-major."""
    res = sinkhorn(jnp.exp(jnp.clip(z[2 * n:], lo, hi)).reshape(n, n, -1),
                   iters, eps)
    return jnp.concatenate([jax.nn.sigmoid(z[:n]),
                            2.0 * jax.nn.sigmoid(z[n:2 * n]),
                            res.reshape(n * n, -1)])


def _maps_kernel(scale_ref, bias_ref, u_ref, o_ref, *, n, iters, eps, lo,
                 hi):
    z = u_ref[...] * scale_ref[...] + bias_ref[...]  # (K, tokens)
    o_ref[0:n, :] = jax.nn.sigmoid(z[0:n])
    o_ref[n:2 * n, :] = 2.0 * jax.nn.sigmoid(z[n:2 * n])
    M = jnp.exp(jnp.clip(z[2 * n:], lo, hi))
    m = [M[k:k + 1, :] for k in range(n * n)]  # entry (i, j) at i n + j
    for _ in range(iters):
        for j in range(n):  # a column: over the rows i
            s = functools.reduce(jnp.add, m[j::n]) + eps
            for i in range(n):
                m[i * n + j] = m[i * n + j] / s
        for i in range(n):  # a row: over the columns j
            s = functools.reduce(jnp.add, m[i * n:(i + 1) * n]) + eps
            for j in range(n):
                m[i * n + j] = m[i * n + j] / s
    for k in range(n * n):
        o_ref[2 * n + k:2 * n + k + 1, :] = m[k]


def mhc_maps(u, scale, bias, *, n: int, iters: int, eps: float, lo: float,
             hi: float, interpret: bool = False):
    """``maps_reference(u * scale + bias)`` as one kernel: u (n^2 + 2n, N)
    float32, scale and bias (n^2 + 2n, 1)."""
    return _maps(u, scale, bias, n=n, iters=iters, eps=eps, lo=lo, hi=hi,
                 interpret=interpret)


@functools.partial(jax.jit, static_argnames=(
    "n", "iters", "eps", "lo", "hi", "interpret"))
def _maps(u, scale, bias, *, n, iters, eps, lo, hi, interpret):
    """Jitted, so that a program's half-layers share ONE traced and
    lowered kernel."""
    K, N = u.shape
    block = N if N <= BLOCK_TOKENS else BLOCK_TOKENS
    padded = -(-N // block) * block
    if padded != N:
        u = jnp.pad(u, ((0, 0), (0, padded - N)))
    column = pl.BlockSpec((K, 1), lambda t: (0, 0))
    tokens = pl.BlockSpec((K, block), lambda t: (0, t))
    out = pl.pallas_call(
        functools.partial(_maps_kernel, n=n, iters=iters, eps=eps, lo=lo,
                          hi=hi),
        out_shape=jax.ShapeDtypeStruct((K, padded), jnp.float32),
        grid=(padded // block,),
        in_specs=[column, column, tokens],
        out_specs=tokens,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        name="mhc_maps",
        interpret=interpret,
    )(scale, bias, u)
    return out[:, :N]
