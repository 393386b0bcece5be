"""The Kimi Delta Attention mixer (Kimi Linear, arXiv:2510.26692): linear
attention whose state a head, ``S`` (d_k, d_v) float32, forgets by a
decay a CHANNEL and learns by the delta rule.

With h a token's normed row, per head (``d_k = d_v = head_dim``):

    q~ | k~ | v~ | f | b | o_gate = h in_proj
    q~, k~, v = SiLU(conv(.))    causal depthwise conv over `conv_kernel` rows
    q = q~ / |q~| / sqrt(d_k)    k = k~ / |k~|    (L2, eps `L2_EPS`)
    g = lower_bound * sigmoid(exp(A_log) * (f + dt_bias))   in (lower_bound, 0)
    beta = sigmoid(b)                                       a scalar a head
    S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t
    y = (sigmoid(o_gate) * rmsnorm_head(o_t)) out_proj

A layer's weights: `in_proj` (D, 4 H d + 2 H: the columns in the order
above), `conv_w` (K, 3 H d), `A_log` (H,), `dt_bias` (H d,), `o_norm` (d,)
and `out_proj` (H d, D).

A lane's recurrent state (serve/llm/cache.py) is the last ``conv_kernel -
1`` conv inputs (a part each, rounded to `dtype` where they are stored, as
models/mamba2.py holds its window; a program's own rows go into the
convolution as the float32 the projection accumulated) and `s`, (heads,
d_k, d_v) float32. Prompts and chunks run the
chunked form with an initial state (`rows`), decode one step of the
recurrence on every slot where the state lies (`step`); rows past
`n_valid` (bucket padding) get ``g = 0, beta = 0`` and leave state and
window alone, and a slot no lane of a decode step owns is written back as
read.

**The chunked form** (`chunked`), a block of `BLOCK` rows at a time with
``G`` the running sum of g inside the block and S_0 the state before it.
``S_t = Diag(exp(G_t)) S_0 + sum_{i<=t} Diag(exp(G_t - G_i)) k_i w_i^T``
where the rows w solve the unit lower-triangular system

    (I + A) W = beta * (V - (K * exp(G)) S_0),
    A[t, i] = beta_t sum_c k_t[c] k_i[c] exp(G_t[c] - G_i[c])    (i < t)

so with ``U | W_k = (I + A)^-1 (beta V | beta K exp(G))`` computed for
every block at once, a `lax.scan` over the blocks carries the state:
``W = U - W_k S_0``, ``o = (Q exp(G)) S_0 + A_qk W`` (A_qk as A with q_t
in k_t's place, no beta, the diagonal kept), ``S = Diag(exp(G_end)) S_0 +
(K exp(G_end - G))^T W``. Every exponent is a DIFFERENCE ``G_t - G_i``
with i <= t, at most 0: ``exp(-G)`` is never formed (at g = -5 a row it
leaves float32 after 18 rows, which is what the gate's lower bound is
there for). Inside a sub-block of `SUB` rows the differences are formed
pair by pair; between sub-blocks they are split at the row sub-block's
first row r, ``exp(G_t - G_r) exp(G_r - G_i)``, both factors at most 1
(one that underflows is a product that would have), so that the sums
over channels are matrix products.

The state, the gate, the norms, the solve and every product inside the
recurrence are float32 (the products at the highest precision); products
of activations with weights are in `dtype`.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

BLOCK = 64  # rows the chunked form solves at once
SUB = 16  # rows whose decays are formed pair by pair
L2_EPS = 1e-6  # under the root of q's and k's L2 norms
_HIGHEST = jax.lax.Precision.HIGHEST


@dataclasses.dataclass(frozen=True)
class KdaSizes:
    heads: int  # H
    head_dim: int  # d: of q, k and v alike
    conv_kernel: int  # K rows
    lower_bound: float  # of the log-decay g a row
    eps: float  # the output norm's
    dtype: Any  # of the products with weights

    @property
    def d_inner(self) -> int:
        return self.heads * self.head_dim

    @property
    def conv_dim(self) -> int:
        return 3 * self.d_inner

    def state_parts(self) -> tuple:
        """(name, shape a lane and layer, dtype) of a layer's recurrent
        state, for `cache.StateLayout`: the conv window a part a row
        (`conv0` the oldest), and the matrix a head."""
        return tuple(
            (f"conv{j}", (self.conv_dim,), self.dtype)
            for j in range(self.conv_kernel - 1)) + (
            ("s", (self.heads, self.head_dim, self.head_dim), jnp.float32),)


def _inputs(h, p, s: KdaSizes):
    """Normed rows h (..., D) -> in float32 (the product's accumulator,
    not rounded) q~ | k~ | v~ before the convolution (..., 3 H d), the
    gate's f (..., H d), b (..., H) and the output gate's logits (...,
    H)."""
    with jax.named_scope("kda.in_proj"):
        proj = jnp.matmul(h, p["in_proj"].astype(s.dtype),
                          preferred_element_type=jnp.float32)
    return jnp.split(
        proj, (s.conv_dim, s.conv_dim + s.d_inner,
               s.conv_dim + s.d_inner + s.heads), axis=-1)


def _gate(f, b, p, s: KdaSizes):
    """-> the log-decay g (..., H, d) in (lower_bound, 0) and beta (...,
    H), float32."""
    with jax.named_scope("kda.gate"):
        rate = jnp.exp(p["A_log"].astype(jnp.float32))[:, None]
        f = (f + p["dt_bias"].astype(jnp.float32)).reshape(
            *f.shape[:-1], s.heads, s.head_dim)
        return s.lower_bound * jax.nn.sigmoid(rate * f), jax.nn.sigmoid(b)


def _qkv(conv, s: KdaSizes):
    """The convolution's sums (..., 3 H d) f32 -> q, k, v (..., H, d) f32:
    SiLU, q and k of unit L2 norm a head, q times ``1 / sqrt(d)``."""
    x = jax.nn.silu(conv).reshape(*conv.shape[:-1], 3, s.heads, s.head_dim)
    q, k, v = x[..., 0, :, :], x[..., 1, :, :], x[..., 2, :, :]

    def unit(a):
        return a * jax.lax.rsqrt(
            jnp.sum(a * a, axis=-1, keepdims=True) + L2_EPS)

    return unit(q) * s.head_dim ** -0.5, unit(k), v


def _output(o, o_gate, p, s: KdaSizes):
    """o (..., H, d) f32 from the recurrence -> the mixer's output (...,
    D): an RMSNorm a head under one learned scale, the head's gate,
    `out_proj`."""
    with jax.named_scope("kda.out"):
        o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                              + s.eps) * p["o_norm"].astype(jnp.float32)
        o = o * jax.nn.sigmoid(o_gate)[..., None]
        return o.reshape(*o.shape[:-2], s.d_inner).astype(s.dtype) \
            @ p["out_proj"].astype(s.dtype)


def _decayed_products(qb, kb, G, sub: int):
    """Per block and head, rows (nb, H, Q, d) f32 and the running log-decay
    G: ``sum_c x_t[c] k_i[c] exp(G_t[c] - G_i[c])`` for i <= t, 0 above the
    diagonal, with x = k and x = q -> two (nb, H, Q, Q)."""
    nb, H, Q, d = kb.shape
    ns = Q // sub

    def subs(x):
        return x.reshape(nb, H, ns, sub, d)

    qs, ks, Gs = subs(qb), subs(kb), subs(G)
    # inside a sub-block: every pair's decay, formed where i <= t
    low = jnp.tril(jnp.ones((sub, sub), bool))[:, :, None]
    decay = jnp.exp(jnp.where(
        low, Gs[:, :, :, :, None] - Gs[:, :, :, None, :], -jnp.inf))
    k_i = ks[:, :, :, None, :] * decay
    # between sub-blocks: split at the row sub-block's first row
    first = Gs[:, :, :, :1]
    to_row = jnp.exp(Gs - first)
    k_col = kb[:, :, None] * jnp.exp(
        jnp.minimum(first - G[:, :, None], 0.0))  # (nb, H, ns, Q, d)
    before = jnp.arange(Q)[None, :] < (jnp.arange(ns) * sub)[:, None]
    own = jnp.eye(ns, dtype=bool)[:, None, :, None]

    def whole(x):
        inside = jnp.sum(x[:, :, :, :, None] * k_i, axis=-1)
        across = jnp.einsum("nhitc,nhijc->nhitj", x * to_row, k_col,
                            precision=_HIGHEST)
        inside = jnp.where(own, inside[:, :, :, :, None], 0.0)
        return (inside.reshape(nb, H, ns, sub, Q)
                + jnp.where(before[:, None], across, 0.0)
                ).reshape(nb, H, Q, Q)

    return whole(ks), whole(qs)


def chunked(q, k, v, g, beta, state):
    """The recurrence over T rows in its chunked form: q, k, v (T, H, d),
    g (T, H, d) at most 0, beta (T, H) (``g = 0, beta = 0``: a row that
    must not count), state (H, d, d), all float32 -> (o (T, H, d), the
    state after the last row). Any T: the last block is padded with rows
    that do not count."""
    T, H, d = q.shape
    Q = min(BLOCK, T)
    sub = SUB if Q % SUB == 0 else Q
    nb = -(-T // Q)

    def blocks(x):
        x = jnp.pad(x, ((0, nb * Q - T),) + ((0, 0),) * (x.ndim - 1))
        return jnp.moveaxis(x.reshape(nb, Q, *x.shape[1:]), 1, 2)

    qb, kb, vb, gb = (blocks(x) for x in (q, k, v, g))  # (nb, H, Q, d)
    bb = blocks(beta[..., None])  # (nb, H, Q, 1)
    G = jnp.cumsum(gb, axis=2)
    a_kk, a_qk = _decayed_products(qb, kb, G, sub)
    grown = jnp.exp(G)
    system = jnp.eye(Q) + bb * jnp.tril(a_kk, -1)
    solved = jax.scipy.linalg.solve_triangular(
        system, jnp.concatenate([bb * vb, bb * kb * grown], axis=-1),
        lower=True, unit_diagonal=True)
    to_end = kb * jnp.exp(G[:, :, -1:] - G)

    def one(S, xs):
        u, w_k, q_grown, a_qk, to_end, at_end = xs
        w = u - jnp.einsum("hqc,hcv->hqv", w_k, S, precision=_HIGHEST)
        o = jnp.einsum("hqc,hcv->hqv", q_grown, S, precision=_HIGHEST) \
            + jnp.einsum("hqi,hiv->hqv", a_qk, w, precision=_HIGHEST)
        S = at_end[..., None] * S + jnp.einsum(
            "hqc,hqv->hcv", to_end, w, precision=_HIGHEST)
        return S, o

    S, o = jax.lax.scan(one, state.astype(jnp.float32), (
        solved[..., :d], solved[..., d:], qb * grown, a_qk, to_end,
        grown[:, :, -1]))
    return jnp.moveaxis(o, 1, 2).reshape(nb * Q, H, d)[:T], S


def one_step(q, k, v, g, beta, S):
    """One row a state: q, k, v, g (..., H, d), beta (..., H), S (..., H,
    d, d), float32 -> (o (..., H, d), the states after the row). The
    read-out is formed from the state BEFORE the update, ``o = (q exp(g))^T
    S + (q . k) w`` with ``w = beta (v - (k exp(g))^T S)``, so that S is
    read once for both sums and once for the update; all of it elementwise
    and float32 (no matrix unit's rounding). The two sums are written
    apart: XLA:TPU makes them one fusion with two results that reads the
    state buffer where it lies, where one sum over the two stacked took a
    copy of the layer's state first (the AOT compile, PR 61). ``g = 0,
    beta = 0, k = 0`` leave S as it is to the bit."""
    decay = jnp.exp(g)
    kept = jnp.sum((k * decay)[..., None] * S, axis=-2)  # (k exp(g))^T S
    read = jnp.sum((q * decay)[..., None] * S, axis=-2)
    w = beta[..., None] * (v - kept)
    o = read + w * jnp.sum(q * k, axis=-1, keepdims=True)
    return o, decay[..., None] * S + k[..., None] * w[..., None, :]


def _window(state: dict, s: KdaSizes):
    """The conv window (..., K-1, C) of a layer's state parts."""
    return jnp.stack([state[f"conv{j}"]
                      for j in range(s.conv_kernel - 1)], axis=-2)


def rows(h, p, s: KdaSizes, view, index: int, n_valid):
    """The mixer on one lane's normed rows h (T, D), from the state in
    the lane's slot (zero on a sequence's first rows) and leaving the
    state after row ``n_valid - 1`` there."""
    T = h.shape[0]
    K = s.conv_kernel
    state = view.lane(index)
    qkv, f, b, o_gate = _inputs(h, p, s)
    with jax.named_scope("kda.conv"):
        # window[j] is the input K-1-j rows back; rows of the lane's
        # earlier programs come from its slot
        # (held in `dtype`; the program's own rows are float32)
        seen = jnp.concatenate([_window(state, s).astype(qkv.dtype), qkv])
        w = p["conv_w"].astype(jnp.float32)
        q, k, v = _qkv(sum(w[j] * seen[j:j + T] for j in range(K)), s)
        # the last K-1 REAL inputs: padded rows leave the window alone
        window = jax.lax.dynamic_slice_in_dim(seen, n_valid, K - 1)
    g, beta = _gate(f, b, p, s)
    real = jnp.arange(T) < n_valid
    g = jnp.where(real[:, None, None], g, 0.0)
    beta = jnp.where(real[:, None], beta, 0.0)
    with jax.named_scope("kda.chunk"):
        o, S = chunked(q, k, v, g, beta, state["s"])
    view.set_lane(index, {"s": S, **{
        f"conv{j}": window[j] for j in range(K - 1)}})
    return _output(o, o_gate, p, s)


def step(h, p, s: KdaSizes, view, index: int):
    """One step of the recurrence for a decode batch h (Sb, D). Both parts
    of the state are updated where they lie, every slot of the layer in
    one pass: a slot that no lane of this step owns keeps its conv window,
    and gets ``g = 0, beta = 0, k = 0``, which leave its S as it is to the
    bit. The jnp form: `ops/ssm_step.py`'s kernel steps a Mamba-2 state
    and declines this one (`steps_by_kernel`: no part named as its)."""
    qkv, f, b, o_gate = _inputs(h, p, s)
    state = view.all(index)
    with jax.named_scope("kda.conv"):
        window = _window(state, s)  # (slots, K-1, C)
        seen = jnp.concatenate(
            [window.astype(qkv.dtype), view.to_slots(qkv)[:, None]], 1)
        for j in range(s.conv_kernel - 1):  # the window moves one row on
            view.set_all(index, f"conv{j}", jnp.where(
                view.owned[:, None], seen[:, j + 1], window[:, j]))
        q, k, v = _qkv(jnp.einsum(
            "kc,bkc->bc", p["conv_w"].astype(jnp.float32),
            view.from_slots(seen)), s)
    g, beta = _gate(f, b, p, s)
    with jax.named_scope("kda.step"):
        # in slot order, zeros where no lane: g = 0, beta = 0, k = 0
        o, new = one_step(*(view.to_slots(x) for x in (q, k, v, g, beta)),
                          state["s"])
        view.set_all(index, "s", new)
        o = view.from_slots(o)
    return _output(o, o_gate, p, s)
