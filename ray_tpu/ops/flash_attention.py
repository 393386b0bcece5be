"""Blockwise causal flash attention — pallas TPU kernels, fwd + bwd.

This is the fused-attention role the reference delegates to
cuDNN/torch SDPA (SURVEY.md §2.5); on TPU we own the kernel. Design
(FlashAttention-2 style, online softmax):

- forward: scratch carries the running max m, sum l and the f32 output
  accumulator over the keys; softmax statistics are float32 always; the
  logsumexp per row is emitted for the backward pass.
- backward: two kernels (no atomics on TPU), dq and dk/dv, after a small
  one for `delta` = rowsum(do * o); both recompute p = exp(s - lse) tile
  by tile, so nothing O(T²) is ever materialized.
- matmuls run on the MXU with preferred_element_type=float32; inputs may
  be bfloat16. `sm_scale` is folded into the q tile (k in dk/dv) once a
  grid block, never into the scores; dq and dk are scaled once when they
  are written.

Layouts (chosen from the shape the call sees; `plan()` says which):

- `dense`: the model's `(B, T, H, D)` is `(B, T, H*D)` for free, and the
  kernels read and write it as it is: no transposing copy in or out.
  D = 64 with H even: a block is 128 lanes wide, TWO heads a block
  (lane-dense, unpadded). The heads are had without slicing lanes: the
  other head's 64 lanes of a tile are zeroed and the product contracts
  over all 128 (the MXU contracts 128 either way); of a 128-wide product
  each head keeps its half. D a multiple of 128 (llama): one head a block.
- `per_head`: every other shape (H odd at D = 64; D = 32, 96, ..): the
  heads are moved beside the batch, `(B*H, T, D)`, by a transposing copy
  each way, one head a block, the block as wide as the head.
- statistics (`lse`, `delta`) are float32 `(G, C, heads a block, T)`: T on
  the LANE axis, 4 bytes a row and head (a trailing dimension of 1 would
  be padded to 128 lanes in HBM: 201 MB where 1.5 are meant).

Two levels of blocks. A GRID block is what a step fetches: `_GRID_ROWS`
(1,024) rows of q and of k (T where T is shorter), so K and V of a whole
1,024-token sequence are fetched once a head pair. Inside it the kernels
walk score TILES with static loops: 128 x 128 in the forward and dk/dv,
256 x 256 in dq (`_TILES`; T/2 at most, 128 at the least), so from T = 256
on a row has several tiles and the causal structure is used:

- a tile strictly above the diagonal is not computed (10 of 16 tiles of
  256 run at T = 1,024, 36 of 64 tiles of 128), and a grid block above it
  (T > 1,024) is skipped (`pl.when`) and fetches nothing: its index map
  is clamped to the diagonal's block, which is not fetched again;
- the mask (an iota difference, a compare, a select) is applied only in
  the tiles the diagonal crosses; tiles below it run unmasked.

The forward and dk/dv hold a score tile TRANSPOSED (keys on rows,
queries on lanes): a row statistic is then a `(1, queries)` row that
broadcasts along sublanes as it arrives, the forward's max and sum reduce
over sublanes by elementwise steps, and dk/dv's four products are plain
ones. dq holds it the usual way round (its three products are then the
plain ones) and turns the two statistics it needs to columns once a grid
block.

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
KERNELS = ("fwd", "dq", "dkv")
# A score tile's (queries, keys) by kernel, at most (`_default_block` says
# how T lowers them), and the rows of a grid block: the unit that is
# fetched, holding whole tiles.
_TILES = {"fwd": (128, 128), "dq": (256, 256), "dkv": (128, 128)}
_GRID_ROWS = 1024
_LANES = 128

_NT = (((1,), (1,)), ((), ()))  # a @ b.T
_NN = (((1,), (0,)), ((), ()))  # a @ b
_TN = (((0,), (0,)), ((), ()))  # a.T @ b


@dataclasses.dataclass(frozen=True)
class _Cfg:
    causal: bool
    sm_scale: float
    block_q: int  # a score tile's queries (its lanes)
    block_k: int  # a score tile's keys (its rows)
    interpret: bool
    head_dim: int
    heads: int  # heads a lane block: 2 at D = 64 in the dense layout
    grid_rows: int  # of a grid block, q and k alike
    one_grid_block: bool = False  # grid_rows == T: none off the diagonal
    # Tests only: mask every tile that runs, as the kernels did before
    # the mask was kept to the diagonal, to show the two equal to the bit.
    mask_every_block: bool = False

    @property
    def width(self) -> int:
        return self.heads * self.head_dim


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, dims,
                               preferred_element_type=jnp.float32)


def _head_tiles(x, cfg: _Cfg):
    """A (rows, W) tile seen by each head of the block: itself for one
    head; for two, with the other head's lanes zeroed, so that a product
    contracting over all W lanes contracts over one head."""
    if cfg.heads == 1:
        return [x]
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    zero = jnp.zeros_like(x)
    return [jnp.where(lane < cfg.head_dim, x, zero),
            jnp.where(lane >= cfg.head_dim, x, zero)]


def _by_head(parts, shape, cfg: _Cfg, axis: int):
    """The tile of `shape` whose W entries along `axis` are, for head h's
    D of them, parts[h]'s: a W-wide product of which only that head's
    half means anything, or a statistic to spread over the head."""
    if cfg.heads == 1:
        return jnp.broadcast_to(parts[0], shape)
    at = jax.lax.broadcasted_iota(jnp.int32, shape, axis)
    return jnp.where(at < cfg.head_dim, parts[0], parts[1])


def _scaled(ref, cfg: _Cfg):
    x = ref[0]
    return (x.astype(jnp.float32) * cfg.sm_scale).astype(x.dtype)


def _tiles(cfg: _Cfg, diagonal: bool, *, by_key: bool = False):
    """The score tiles of a grid block that run, grouped as a kernel
    walks them: {query tile a: [(key tile b, masked), ..]}, or with
    `by_key` {b: [(a, masked), ..]}. Below the diagonal all of them,
    unmasked; in a grid block the diagonal crosses, none above it, and
    masked only where it crosses the tile."""
    bq, bk = cfg.block_q, cfg.block_k
    out: dict = {}
    for a in range(cfg.grid_rows // bq):
        for b in range(cfg.grid_rows // bk):
            if not (diagonal and cfg.causal):
                masked = cfg.causal and cfg.mask_every_block
            elif b * bk <= a * bq + bq - 1:
                below = b * bk + bk - 1 <= a * bq
                masked = cfg.mask_every_block or not below
            else:
                continue
            major, minor = (b, a) if by_key else (a, b)
            out.setdefault(major, []).append((minor, masked))
    return out


def _visible(cfg: _Cfg, offset, *, keys_on_rows: bool):
    """Where key <= query in a score tile whose first query is `offset`
    positions after its first key."""
    shape = (cfg.block_k, cfg.block_q) if keys_on_rows else (
        cfg.block_q, cfg.block_k)
    kdim, qdim = (0, 1) if keys_on_rows else (1, 0)
    d = (jax.lax.broadcasted_iota(jnp.int32, shape, kdim)
         - jax.lax.broadcasted_iota(jnp.int32, shape, qdim))
    return d <= offset


def _each_grid_block(qi, ki, cfg: _Cfg, block):
    """Run `block(diagonal)` as grid block (qi, ki) needs: not at all
    above the diagonal, the tiles up to the diagonal on it, all below."""
    if not cfg.causal:
        block(False)
    elif cfg.one_grid_block:
        block(True)
    else:
        pl.when(ki < qi)(lambda: block(False))
        pl.when(ki == qi)(lambda: block(True))


def _rows(n, size):
    return slice(n * size, (n + 1) * size)


# ---------------------------------------------------------------- forward
#
# The work on one score tile is a jitted function of values: a kernel
# calls it once a tile from static loops, so the body is traced once a
# kernel and kind of tile (masked or not) and not once a tile (36 tiles of
# two heads each in the forward at T = 1,024: traced tile by tile the
# train step took six times as long to trace).


@functools.partial(jax.jit, static_argnames=("cfg", "masked"))
def _fwd_tile(k, v, qs, m, l, o_t, offset, *, cfg: _Cfg, masked: bool):
    """Keys on rows, queries on lanes: a row statistic is a (1, bq) row,
    reduced over sublanes by elementwise steps. `qs`: (heads, bq, W), the
    scaled q tile as each head sees it; `m`, `l`: a (1, bq) row a head;
    `o_t`: (W, bq), the output accumulator, turned when it is written."""
    alphas, pvs = [], []
    m, l = list(m), list(l)
    for h in range(cfg.heads):
        st = _dot(k, qs[h], _NT)  # (bk, bq) f32
        if masked:
            st = jnp.where(_visible(cfg, offset, keys_on_rows=True), st,
                           DEFAULT_MASK_VALUE)
        m_new = jnp.maximum(m[h], jnp.max(st, axis=0, keepdims=True))
        alpha = jnp.exp(m[h] - m_new)
        pt = jnp.exp(st - m_new)
        l[h] = alpha * l[h] + jnp.sum(pt, axis=0, keepdims=True)
        m[h] = m_new
        alphas.append(alpha)
        pvs.append(_dot(v, pt.astype(v.dtype), _TN))  # (W, bq)
    o_t = (o_t * _by_head(alphas, o_t.shape, cfg, 0)
           + _by_head(pvs, o_t.shape, cfg, 0))
    return m, l, o_t


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, qs, acc, m_s, l_s, *,
                cfg: _Cfg):
    qi, ki = pl.program_id(2), pl.program_id(3)
    bq, bk = cfg.block_q, cfg.block_k
    heads = range(cfg.heads)

    @pl.when(ki == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s, -jnp.inf)
        l_s[...] = jnp.zeros_like(l_s)
        acc[...] = jnp.zeros_like(acc)
        for h, t in enumerate(_head_tiles(_scaled(q_ref, cfg), cfg)):
            qs[h] = t

    def _block(diagonal):
        ahead = 0 if diagonal else (qi - ki) * cfg.grid_rows
        for a, keys in _tiles(cfg, diagonal).items():
            qa = _rows(a, bq)
            m = [m_s[h:h + 1, qa] for h in heads]  # (1, bq)
            l = [l_s[h:h + 1, qa] for h in heads]
            o_t = acc[:, qa]  # (W, bq)
            for b, masked in keys:
                kb = _rows(b, bk)
                m, l, o_t = _fwd_tile(
                    k_ref[0, kb, :], v_ref[0, kb, :], qs[:, qa, :], m, l,
                    o_t, a * bq - b * bk + ahead, cfg=cfg, masked=masked)
            acc[:, qa] = o_t
            for h in heads:
                m_s[h:h + 1, qa] = m[h]
                l_s[h:h + 1, qa] = l[h]

    _each_grid_block(qi, ki, cfg, _block)

    @pl.when(ki == pl.num_programs(3) - 1)
    def _emit():
        l = jnp.where(l_s[...] == 0.0, 1.0, l_s[...])  # (heads, rows)
        lse_ref[0, 0] = m_s[...] + jnp.log(l)
        ls = [l[h:h + 1, :] for h in heads]
        o_ref[0] = (acc[...] / _by_head(ls, acc.shape, cfg, 0)).T.astype(
            o_ref.dtype)


def _call(kernel, q, cfg: _Cfg, *, k_major: bool, ins, outs, out_dtypes,
          scratch):
    """The `pallas_call` of a kernel over `(G, T, C*W)` arrays ("q": the
    blocks that follow the queries, q, o, do, dq; "k": those that follow
    the keys, k, v, dk, dv) and `(G, C, heads, T)` statistics ("stat",
    which follow the queries). `k_major` (dk/dv): k blocks outer, q blocks
    innermost. A step above the diagonal is handed the block of the
    nearest step that runs, which is then not fetched again."""
    G, T, CW = q.shape
    W, R = cfg.width, cfg.grid_rows
    n = T // R

    def blocks(x, y):  # -> (q block, k block) of a step
        i, j = (y, x) if k_major else (x, y)
        if cfg.causal and not k_major:
            j = jnp.minimum(j, i)
        if cfg.causal and k_major:
            i = jnp.maximum(i, j)
        return i, j

    spec = functools.partial(pl.BlockSpec, memory_space=pltpu.VMEM)
    specs = {
        "q": spec((1, R, W), lambda g, c, x, y: (g, blocks(x, y)[0], c)),
        "k": spec((1, R, W), lambda g, c, x, y: (g, blocks(x, y)[1], c)),
        "stat": spec((1, 1, cfg.heads, R),
                     lambda g, c, x, y: (g, c, 0, blocks(x, y)[0])),
    }
    shapes = {"q": q.shape, "k": q.shape, "stat": (G, CW // W, cfg.heads, T)}
    return pl.pallas_call(
        functools.partial(kernel, cfg=cfg),
        grid=(G, CW // W, n, n),
        in_specs=[specs[name] for name in ins],
        out_specs=[specs[name] for name in outs],
        out_shape=[jax.ShapeDtypeStruct(shapes[name], dt)
                   for name, dt in zip(outs, out_dtypes)],
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(dimension_semantics=(
            "parallel", "parallel", "parallel", "arbitrary")),
        interpret=cfg.interpret,
    )


def _fwd(q, k, v, cfg: _Cfg):
    R, W = cfg.grid_rows, cfg.width
    o, lse = _call(
        _fwd_kernel, q, cfg, k_major=False,
        ins=("q", "k", "k"), outs=("q", "stat"),
        out_dtypes=(q.dtype, jnp.float32),
        scratch=[
            pltpu.VMEM((cfg.heads, R, W), q.dtype),
            pltpu.VMEM((W, R), jnp.float32),
            pltpu.VMEM((cfg.heads, R), jnp.float32),
            pltpu.VMEM((cfg.heads, R), jnp.float32),
        ])(q, k, v)
    return o, lse


# ---------------------------------------------------------------- backward


def _column(row):
    """(1, n) with n on lanes -> (n, 128) with n on sublanes, every lane
    the same."""
    return jnp.broadcast_to(row, (_LANES, row.shape[1])).T


@functools.partial(jax.jit, static_argnames=("cfg", "masked"))
def _dq_tile(k, v, qs, dos, lse, delta, dq_a, offset, *, cfg: _Cfg,
             masked: bool):
    """Queries on rows, keys on lanes, so that the three products are
    plain ones. `qs`, `dos`: (heads, bq, W) as each head sees them; `lse`,
    `delta`: (heads, bq, 128) columns, every lane the same; `dq_a`:
    (bq, W)."""
    bk = cfg.block_k

    def cols(x):  # (bq, 128) every lane the same -> (bq, bk)
        if bk <= _LANES:
            return x[:, :bk]
        return jnp.concatenate([x] * (bk // _LANES), axis=1)

    dqs = []
    for h in range(cfg.heads):
        s = _dot(qs[h], k, _NT)  # (bq, bk)
        p = jnp.exp(s - cols(lse[h]))
        if masked:
            p = jnp.where(_visible(cfg, offset, keys_on_rows=False), p, 0.0)
        dp = _dot(dos[h], v, _NT)
        ds = p * (dp - cols(delta[h]))
        dqs.append(_dot(ds.astype(k.dtype), k, _NN))  # (bq, W)
    return dq_a + _by_head(dqs, dq_a.shape, cfg, 1)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               qs, dos, lse_c, delta_c, dq_acc, *, cfg: _Cfg):
    qi, ki = pl.program_id(2), pl.program_id(3)
    bq, bk = cfg.block_q, cfg.block_k

    @pl.when(ki == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)
        for h, t in enumerate(_head_tiles(_scaled(q_ref, cfg), cfg)):
            qs[h] = t
        for h, t in enumerate(_head_tiles(do_ref[0], cfg)):
            dos[h] = t
        for h in range(cfg.heads):
            lse_c[h] = _column(lse_ref[0, 0, h:h + 1, :])
            delta_c[h] = _column(delta_ref[0, 0, h:h + 1, :])

    def _block(diagonal):
        ahead = 0 if diagonal else (qi - ki) * cfg.grid_rows
        for a, keys in _tiles(cfg, diagonal).items():
            qa = _rows(a, bq)
            dq_a = dq_acc[qa, :]  # (bq, W)
            for b, masked in keys:
                kb = _rows(b, bk)
                dq_a = _dq_tile(
                    k_ref[0, kb, :], v_ref[0, kb, :], qs[:, qa, :],
                    dos[:, qa, :], lse_c[:, qa, :], delta_c[:, qa, :], dq_a,
                    a * bq - b * bk + ahead, cfg=cfg, masked=masked)
            dq_acc[qa, :] = dq_a

    _each_grid_block(qi, ki, cfg, _block)

    @pl.when(ki == pl.num_programs(3) - 1)
    def _emit():
        dq_ref[0] = (dq_acc[...] * cfg.sm_scale).astype(dq_ref.dtype)


@functools.partial(jax.jit, static_argnames=("cfg", "masked"))
def _dkv_tile(q, do, ks, vs, lse, delta, dk_t, dv_t, offset, *, cfg: _Cfg,
              masked: bool):
    """Keys on rows, queries on lanes: the (heads, bq) statistics
    broadcast along sublanes as they arrive, and the four products are
    plain ones. `ks`, `vs`: (heads, bk, W) as each head sees them, k
    scaled; `dk_t`, `dv_t`: (bk, W)."""
    dks, dvs = [], []
    for h in range(cfg.heads):
        st = _dot(ks[h], q, _NT)  # (bk, bq)
        pt = jnp.exp(st - lse[h:h + 1, :])
        if masked:
            pt = jnp.where(_visible(cfg, offset, keys_on_rows=True), pt, 0.0)
        dvs.append(_dot(pt.astype(do.dtype), do, _NN))  # (bk, W)
        dpt = _dot(vs[h], do, _NT)
        dst = pt * (dpt - delta[h:h + 1, :])
        dks.append(_dot(dst.astype(q.dtype), q, _NN))
    return (dk_t + _by_head(dks, dk_t.shape, cfg, 1),
            dv_t + _by_head(dvs, dv_t.shape, cfg, 1))


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref,
                dv_ref, ks, vs, dk_acc, dv_acc, *, cfg: _Cfg):
    ki, qi = pl.program_id(2), pl.program_id(3)
    bq, bk = cfg.block_q, cfg.block_k

    @pl.when(qi == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)
        for h, t in enumerate(_head_tiles(_scaled(k_ref, cfg), cfg)):
            ks[h] = t
        for h, t in enumerate(_head_tiles(v_ref[0], cfg)):
            vs[h] = t

    def _block(diagonal):
        ahead = 0 if diagonal else (qi - ki) * cfg.grid_rows
        for b, queries in _tiles(cfg, diagonal, by_key=True).items():
            kb = _rows(b, bk)
            dk_t, dv_t = dk_acc[kb, :], dv_acc[kb, :]  # (bk, W)
            for a, masked in queries:
                qa = _rows(a, bq)
                dk_t, dv_t = _dkv_tile(
                    q_ref[0, qa, :], do_ref[0, qa, :], ks[:, kb, :],
                    vs[:, kb, :], lse_ref[0, 0, :, qa], delta_ref[0, 0, :, qa],
                    dk_t, dv_t, a * bq - b * bk + ahead, cfg=cfg,
                    masked=masked)
            dk_acc[kb, :] = dk_t
            dv_acc[kb, :] = dv_t

    _each_grid_block(qi, ki, cfg, _block)

    @pl.when(qi == pl.num_programs(3) - 1)
    def _emit():
        dk_ref[0] = (dk_acc[...] * cfg.sm_scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _delta_kernel(do_ref, o_ref, delta_ref, *, cfg: _Cfg):
    prod = (do_ref[0].astype(jnp.float32) * o_ref[0].astype(jnp.float32)).T
    for h in range(cfg.heads):  # (W, rows): a head's D rows summed
        delta_ref[0, 0, h:h + 1, :] = jnp.sum(
            prod[h * cfg.head_dim:(h + 1) * cfg.head_dim, :], axis=0,
            keepdims=True)


def _delta(do, o, cfg: _Cfg):
    """rowsum(do * o) a head, laid out as lse is: (G, C, heads, T). A
    kernel of its own because XLA, asked for this reduction over 64 of a
    row's lanes, first writes the float32 product out and relays it (four
    passes over 100 MB at the train cells' shape where this makes one)."""
    G, T, CW = do.shape
    W, R = cfg.width, cfg.grid_rows
    spec = functools.partial(pl.BlockSpec, memory_space=pltpu.VMEM)
    rows = spec((1, R, W), lambda g, c, i: (g, i, c))
    return pl.pallas_call(
        functools.partial(_delta_kernel, cfg=cfg),
        grid=(G, CW // W, T // R),
        in_specs=[rows, rows],
        out_specs=spec((1, 1, cfg.heads, R), lambda g, c, i: (g, c, 0, i)),
        out_shape=jax.ShapeDtypeStruct((G, CW // W, cfg.heads, T),
                                       jnp.float32),
        interpret=cfg.interpret,
    )(do, o)


_BWD_INS = ("q", "k", "k", "q", "stat", "stat")  # q, k, v, do, lse, delta


def _dq(q, k, v, do, lse, delta, cfg: _Cfg):
    R, W = cfg.grid_rows, cfg.width
    dq, = _call(
        _dq_kernel, q, cfg, k_major=False, ins=_BWD_INS, outs=("q",),
        out_dtypes=(q.dtype,),
        scratch=[
            pltpu.VMEM((cfg.heads, R, W), q.dtype),
            pltpu.VMEM((cfg.heads, R, W), do.dtype),
            pltpu.VMEM((cfg.heads, R, _LANES), jnp.float32),
            pltpu.VMEM((cfg.heads, R, _LANES), jnp.float32),
            pltpu.VMEM((R, W), jnp.float32),
        ])(q, k, v, do, lse, delta)
    return dq


def _dkv(q, k, v, do, lse, delta, cfg: _Cfg):
    R, W = cfg.grid_rows, cfg.width
    return _call(
        _dkv_kernel, q, cfg, k_major=True, ins=_BWD_INS, outs=("k", "k"),
        out_dtypes=(k.dtype, v.dtype),
        scratch=[
            pltpu.VMEM((cfg.heads, R, W), k.dtype),
            pltpu.VMEM((cfg.heads, R, W), v.dtype),
            pltpu.VMEM((R, W), jnp.float32),
            pltpu.VMEM((R, W), jnp.float32),
        ])(q, k, v, do, lse, delta)


def _bwd(q, k, v, o, lse, do, cfgs):
    _, dq_cfg, dkv_cfg = cfgs
    delta = _delta(do, o, dq_cfg)
    dk, dv = _dkv(q, k, v, do, lse, delta, dkv_cfg)
    return _dq(q, k, v, do, lse, delta, dq_cfg), dk, dv


# ---------------------------------------------------------------- public


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _flash(q, k, v, cfgs):
    """`cfgs`: a `_Cfg` a kernel, in `KERNELS`' order."""
    o, _ = _fwd(q, k, v, cfgs[0])
    return o


def _flash_fwd(q, k, v, cfgs):
    o, lse = _fwd(q, k, v, cfgs[0])
    # Name the kernel outputs so a remat policy can SAVE them: under
    # jax.checkpoint(block) the backward replay would otherwise re-run
    # this pallas forward just to rebuild (o, lse) residuals — the
    # lse-saving policy (models.gpt2 remat_policy="save_flash") keeps
    # them and the replay's flash fwd is dead-code-eliminated.
    from jax.ad_checkpoint import checkpoint_name

    o_res = checkpoint_name(o, "flash_o")
    lse = checkpoint_name(lse, "flash_lse")
    return o, (q, k, v, o_res, lse)


def _flash_bwd(cfgs, res, do):
    q, k, v, o, lse = res
    return _bwd(q, k, v, o, lse, do, cfgs)


_flash.defvjp(_flash_fwd, _flash_bwd)


def _fit_block(T: int, want: int) -> int:
    """Largest power-of-two block <= want that divides T (so e.g. T=1536
    runs with 512 blocks instead of failing a 1024 request)."""
    b = min(want, T)
    while b > 128 and T % b:
        b //= 2
    return b


def _default_block(T: int, want: int) -> int:
    """`want`, lowered to at most T/2 where T allows (128 at the least), so
    that a row has several tiles and those above the diagonal are skipped."""
    b = want
    while b > 128 and b > T // 2:
        b //= 2
    return b


def plan(T: int, H: int, D: int, *, causal: bool = True,
         block_q: int | None = None, block_k: int | None = None) -> dict:
    """What `flash_attention` does at sequence length T with H heads of
    width D (the per-shard H under a mesh), from the shape alone: the
    layout, the heads a lane block, and for each kernel its score tile,
    the rows of its grid blocks, how many of the tiles run and how many
    of those are masked. `block_q` / `block_k` give all three kernels one
    tile. Raises ValueError where T does not divide into the tiles."""
    heads = 2 if D == 64 and H % 2 == 0 else 1
    dense = heads == 2 or D % _LANES == 0
    kernels = {}
    for name in KERNELS:
        want_q, want_k = _TILES[name]
        bq = _fit_block(T, block_q or _default_block(T, want_q))
        bk = _fit_block(T, block_k or _default_block(T, want_k))
        if T % bq or T % bk:
            raise ValueError(f"T={T} not divisible by blocks ({bq},{bk})")
        rows = max(_fit_block(T, _GRID_ROWS), bq, bk)
        if rows % bq or rows % bk:
            rows = T
        nq, nk = T // bq, T // bk
        run = masked = 0
        for i in range(nq):
            for j in range(nk):
                runs = not causal or j * bk <= i * bq + bq - 1
                below = not causal or j * bk + bk - 1 <= i * bq
                run += runs
                masked += runs and not below
        kernels[name] = {
            "block_q": bq, "block_k": bk, "grid_rows": rows,
            "steps_run": run, "steps_masked": masked,
            "steps_in_grid": nq * nk, "run_share": run / (nq * nk)}
    return {"layout": "dense" if dense else "per_head",
            "heads_per_block": heads, "kernels": kernels}


def flash_attention(q, k, v, *, causal: bool = True,
                    sm_scale: float | None = None,
                    block_q: int | None = None, block_k: int | None = None,
                    interpret: bool = False) -> jax.Array:
    """q,k,v: (B, T, H, D) -> (B, T, H, D).

    Differentiable (custom VJP with flash backward kernels). Requires T
    divisible by the block sizes (the dispatcher in ops.attention falls
    back to the einsum path otherwise). Layout and tiles follow from
    the shape (`plan`, and the module docstring): D = 64 with H even, and
    D a multiple of 128, are read and written where they lie; any other
    shape is transposed to (B*H, T, D) and back."""
    B, T, H, D = q.shape
    p = plan(T, H, D, causal=causal, block_q=block_q, block_k=block_k)
    if sm_scale is None:
        sm_scale = 1.0 / (D ** 0.5)
    cfgs = tuple(
        _Cfg(causal=causal, sm_scale=float(sm_scale), block_q=t["block_q"],
             block_k=t["block_k"], interpret=interpret, head_dim=D,
             heads=p["heads_per_block"], grid_rows=t["grid_rows"],
             one_grid_block=t["grid_rows"] == T)
        for t in (p["kernels"][name] for name in KERNELS))
    if p["layout"] == "dense":
        def fold(t):  # free: (B, T, H, D) is (B, T, H*D) in memory
            return t.reshape(B, T, H * D)

        return _flash(fold(q), fold(k), fold(v), cfgs).reshape(B, T, H, D)

    def to_bh(t):  # (B,T,H,D) -> (B*H, T, D)
        return t.transpose(0, 2, 1, 3).reshape(B * H, T, D)

    o = _flash(to_bh(q), to_bh(k), to_bh(v), cfgs)
    return o.reshape(B, H, T, D).transpose(0, 2, 1, 3)
