"""The Mamba-2 mixer, once, for every family that has one.

``z | xBC | dt = in_proj(h)``, a causal depthwise convolution over xBC
(with a bias where the layer's weights carry `conv_b`) then SiLU, the
selective state-space recurrence per head
``S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T``, ``y_t = S_t C_t + D x_t``
(B and C shared by the heads of a group), the gate ``y * silu(z)`` under
an RMSNorm a group with a learned scale (ONE group: the norm is over all
of `d_inner`), ``out_proj``.

nemotron_h runs it at 64 heads in 8 groups, granite_hybrid at 128 heads
in one group with a conv bias: a family says its sizes (`Mamba2Sizes`,
its config's `mamba`) and holds a layer's weights under the names
`in_proj`, `conv_w`, `conv_b` (optional), `dt_bias`, `A_log`, `D`,
`gate_norm`, `out_proj`.

A lane's recurrent state (serve/llm/cache.py) is the last
``conv_kernel - 1`` conv inputs (a part each) and the ``(heads,
head_dim, state)`` SSM state, in float32 as the decay is. Prompts and
chunks run the chunked (SSD) form of the recurrence at `chunk` rows with
an initial state (`rows`); decode runs one step of the recurrence on
every slot where the state lies (`step`). Rows past `n_valid` (bucket
padding) get ``dt = 0`` and leave the conv window alone, and a slot no
lane of a decode step owns is written back as read.

Matrix products are in `dtype` (bf16: float32 accumulation on the MXU);
the state, the decay, the convolution's sum and the norm are float32.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from ray_tpu.ops import ssm_step


@dataclasses.dataclass(frozen=True)
class Mamba2Sizes:
    heads: int  # H
    head_dim: int  # P
    state: int  # N
    groups: int  # G: heads h * G / H .. share group g's B and C
    conv_kernel: int  # K rows
    chunk: int  # rows a chunk of the chunked form
    eps: float  # the gated norm's
    dtype: Any  # of the matrix products

    @property
    def d_inner(self) -> int:
        return self.heads * self.head_dim

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.groups * self.state

    def state_parts(self) -> tuple:
        """(name, shape a lane and layer, dtype) of a layer's recurrent
        state, for `cache.StateLayout`: the conv window a part a row
        (`conv0` the oldest), so that each buffer is (layers, slots,
        conv_dim) and tiles without padding (a (3, conv_dim) window a
        slot pads 3 rows to 16 and XLA relays the buffer out around
        every program), and the SSM state."""
        return tuple(
            (f"conv{j}", (self.conv_dim,), self.dtype)
            for j in range(self.conv_kernel - 1)) + (
            ("ssm", (self.heads, self.head_dim, self.state), jnp.float32),)


def _inputs(h, p, s: Mamba2Sizes):
    """Normed rows h (..., D) -> z (..., d_inner), xBC (..., conv_dim)
    before the convolution, dt (..., H) before its bias."""
    with jax.named_scope("ssm.in_proj"):
        zxbcdt = h @ p["in_proj"].astype(s.dtype)
    return jnp.split(zxbcdt, (s.d_inner, s.d_inner + s.conv_dim), axis=-1)


def _conv_bias(p):
    """The convolution's bias (C,) f32, where the layer has one."""
    return p["conv_b"].astype(jnp.float32) if "conv_b" in p else None


def _split(xbc, dt, p, s: Mamba2Sizes):
    """The convolution's output (..., conv_dim) f32 and raw dt -> x
    (..., H, P), B and C (..., G, N) in `dtype`, dt (..., H) f32 after
    its bias and softplus, A (H,) f32."""
    H, P, G, N = s.heads, s.head_dim, s.groups, s.state
    xbc = jax.nn.silu(xbc).astype(s.dtype)
    x, B, C = jnp.split(xbc, (H * P, H * P + G * N), axis=-1)
    lead = xbc.shape[:-1]
    dt = jax.nn.softplus(dt.astype(jnp.float32)
                         + p["dt_bias"].astype(jnp.float32))
    A = -jnp.exp(p["A_log"].astype(jnp.float32))
    return (x.reshape(*lead, H, P), B.reshape(*lead, G, N),
            C.reshape(*lead, G, N), dt, A)


def _output(y, x, z, p, s: Mamba2Sizes):
    """y (..., H, P) f32 from the recurrence -> the mixer's output
    (..., D): the skip ``D x``, the gate under its grouped norm, and
    `out_proj`."""
    G = s.groups
    with jax.named_scope("ssm.gate_norm"):
        y = y + p["D"].astype(jnp.float32)[:, None] * x.astype(jnp.float32)
        lead = y.shape[:-2]
        y = y.reshape(*lead, s.d_inner) \
            * jax.nn.silu(z.astype(jnp.float32))
        g = y.reshape(*lead, G, s.d_inner // G)
        g = g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True)
                              + s.eps)
        y = (g.reshape(*lead, s.d_inner)
             * p["gate_norm"].astype(jnp.float32)).astype(s.dtype)
    with jax.named_scope("ssm.out_proj"):
        return y @ p["out_proj"].astype(s.dtype)


def ssd_chunked(x, B, C, dt, A, state, chunk: int):
    """The recurrence over T rows in its chunked form. x (T, H, P), B and
    C (T, G, N), dt (T, H) f32 (0 for a row that must not count), A (H,),
    state (H, P, N) f32 -> (y (T, H, P) f32 without the skip, the state
    after the last row). Inside a chunk of Q rows, with ``a = dt A`` and
    ``cum`` its running sum: ``y_t = sum_{s<=t} exp(cum_t - cum_s)
    (C_t . B_s) dt_s x_s + exp(cum_t) S_0 C_t`` and ``S_Q = exp(cum_Q)
    S_0 + sum_s exp(cum_Q - cum_s) dt_s x_s B_s^T``; a `lax.scan` carries
    the state from chunk to chunk. The decay and the state are float32;
    the products take their operands as the backend's default precision
    gives them (bf16 on the MXU) and accumulate in float32."""
    T, H, P = x.shape
    G, N = B.shape[1:]
    R = H // G  # heads that share a group's B and C
    Q = chunk if T % chunk == 0 else T
    if T % Q or (Q != chunk and T > chunk):
        raise ValueError(f"{T} rows do not divide into chunks of {chunk}")
    f32 = jnp.float32
    xdt = (x.astype(f32) * dt[..., None]).reshape(T // Q, Q, G, R, P)
    a = (dt * A).reshape(T // Q, Q, G, R)
    Bc = B.astype(f32).reshape(T // Q, Q, G, N)
    Cc = C.astype(f32).reshape(T // Q, Q, G, N)
    causal = jnp.tril(jnp.ones((Q, Q), bool))

    def one(S, xs):
        xdt, a, Bq, Cq = xs
        cum = jnp.cumsum(a, axis=0)  # (Q, G, R)
        # (G, R, t, s): the decay from row s to row t, 0 above the diagonal
        seg = cum.transpose(1, 2, 0)[:, :, :, None] \
            - cum.transpose(1, 2, 0)[:, :, None, :]
        decay = jnp.exp(jnp.where(causal, seg, -jnp.inf))
        cb = jnp.einsum("tgn,sgn->gts", Cq, Bq)
        y = jnp.einsum("grts,sgrp->tgrp", cb[:, None] * decay, xdt)
        y = y + jnp.exp(cum)[..., None] * jnp.einsum(
            "tgn,grpn->tgrp", Cq, S)
        to_end = jnp.exp(cum[-1][None] - cum)  # (Q, G, R)
        S = jnp.exp(cum[-1])[..., None, None] * S + jnp.einsum(
            "sgrp,sgn->grpn", xdt * to_end[..., None], Bq)
        return S, y

    S, y = jax.lax.scan(one, state.astype(f32).reshape(G, R, P, N),
                        (xdt, a, Bc, Cc))
    return y.reshape(T, H, P), S.reshape(H, P, N)


def _row_major(ssm):
    """The lane's new state (H, P, N), laid out as the buffer it is
    written into. At ONE group the TPU compiler makes the chunked form's
    state update a convolution whose result lies P-major of H, takes that
    layout for the whole state buffer, and copies the buffer in and out
    around every prompt's and chunk's program (2 x 2.4 GB at the Granite
    cut: the AOT compile, PR 48); pinned here it relays the lane's 4 MB
    out instead. Several groups (nemotron_h) come out row-major as they
    are."""
    from jax.experimental.layout import Layout, with_layout_constraint

    return with_layout_constraint(ssm, Layout(major_to_minor=(0, 1, 2)))


def _window(state: dict, s: Mamba2Sizes):
    """The conv window (..., K-1, C) of a layer's state parts."""
    return jnp.stack([state[f"conv{j}"]
                      for j in range(s.conv_kernel - 1)], axis=-2)


def rows(h, p, s: Mamba2Sizes, view, index: int, n_valid):
    """The mixer on one lane's normed rows h (T, D), from the state in
    the lane's slot (zero on a sequence's first rows) and leaving the
    state after row ``n_valid - 1`` there."""
    T = h.shape[0]
    K = s.conv_kernel
    state = view.lane(index)
    z, xbc, dt = _inputs(h, p, s)
    with jax.named_scope("ssm.conv"):
        # window[j] is the input K-1-j rows back; rows of the lane's
        # earlier programs come from its slot
        seen = jnp.concatenate([_window(state, s).astype(xbc.dtype), xbc])
        w = p["conv_w"].astype(jnp.float32)
        bias = _conv_bias(p)
        conv = sum(w[j] * seen[j:j + T].astype(jnp.float32)
                   for j in range(K))
        conv = conv if bias is None else bias + conv
        # the last K-1 REAL inputs: padded rows leave the window alone
        window = jax.lax.dynamic_slice_in_dim(seen, n_valid, K - 1)
    x, B, C, dt, A = _split(conv, dt, p, s)
    dt = jnp.where(jnp.arange(T)[:, None] < n_valid, dt, 0.0)
    with jax.named_scope("ssm.scan"):
        y, ssm = ssd_chunked(x, B, C, dt, A, state["ssm"], s.chunk)
        if s.groups == 1:
            ssm = _row_major(ssm)
    view.set_lane(index, {"ssm": ssm, **{
        f"conv{j}": window[j] for j in range(K - 1)}})
    return _output(y, x, z, p, s)


def step(h, p, s: Mamba2Sizes, view, index: int):
    """One step of the recurrence for a decode batch h (Sb, D). Both parts
    of the state are updated where they lie, every slot of the layer in
    one pass: a slot that no lane of this step owns keeps its conv window,
    and gets dt = 0 and x = 0, and ``1 * S + 0`` is S to the bit. Where
    `ssm_step.steps_by_kernel` allows, the SSM state's pass is the Pallas
    kernel's (`ops/ssm_step.py`: the read-out formed inside the in-place
    update); elsewhere the jnp form below, whose read-out XLA makes a
    second pass over the state."""
    G = s.groups
    R = s.heads // G
    z, xbc, dt = _inputs(h, p, s)
    state = view.all(index)
    with jax.named_scope("ssm.conv"):
        window = _window(state, s)  # (slots, K-1, C)
        seen = jnp.concatenate(
            [window, view.to_slots(xbc).astype(window.dtype)[:, None]], 1)
        for j in range(s.conv_kernel - 1):  # the window moves one row on
            view.set_all(index, f"conv{j}", jnp.where(
                view.owned[:, None], seen[:, j + 1], window[:, j]))
        bias = _conv_bias(p)
        conv = jnp.einsum("kc,bkc->bc", p["conv_w"].astype(jnp.float32),
                          view.from_slots(seen).astype(jnp.float32))
        conv = conv if bias is None else bias + conv
    x, B, C, dt, A = _split(conv, dt, p, s)
    with jax.named_scope("ssm.step"):
        f32 = jnp.float32
        dts = view.to_slots(dt)  # (slots, H); 0 where no lane
        xdt = view.to_slots(x.astype(f32)) * dts[..., None]
        Bs = view.to_slots(B.astype(f32))  # (slots, G, N)
        Cs = view.to_slots(C.astype(f32))
        if ssm_step.steps_by_kernel(view.layout):
            y = view.in_place("ssm", lambda buf: ssm_step.ssm_step(
                buf, index, jnp.exp(dts * A), xdt, Bs, Cs))
        else:
            S = state["ssm"]  # (slots, H, P, N) f32
            slots, H, P, N = S.shape
            S5 = S.reshape(slots, G, R, P, N)
            new = jnp.exp(dts * A).reshape(slots, G, R, 1, 1) * S5 \
                + xdt.reshape(slots, G, R, P, 1) * Bs[:, :, None, None, :]
            y = jnp.sum(new * Cs[:, :, None, None, :], axis=-1)
            view.set_all(index, "ssm", new.reshape(S.shape))
            y = y.reshape(slots, H, P)
        y = view.from_slots(y)
    return _output(y, x, z, p, s)
