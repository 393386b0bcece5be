"""Every half-layer of the served xing4 program against the plain
reference's, half-layer by half-layer on the reference's own residual
states, at the widths and on the weights the engine serves, on at least
8,448 rows.

Why the cell needs it beside the log-prob comparison: the harness's four
check prompts are 24 to 300 tokens, shorter than YaRN's original 4,096
positions and than one tile of the dense latent read, so their log-probs
cannot see the scaled frequencies, the read's groups or a long lane's
length; the streams' maps end in bf16 states twelve times a token, which a
log-prob at the end of the stack cannot tell from a wrong coefficient; and,
as in the other shared-layer cuts (PERF.md section 6, PRs 32, 34, 40), the
held experts are a quarter of the routed sum. Fed the SAME states, rounded
once to the program's dtype, the two sides see equal inputs, no difference
is carried from one half-layer to the next, and what is left is rounding.

The program's side is made of the family's own functions
(`ray_tpu.models.xing4`: `mhc_coefficients`, `mhc_pre`, `mhc_post`,
`_attend_cached`; `ray_tpu.models.mla`: `dense`, `experts`) and the serve
path's own dense read of the cached context (`ops/context_attention.py`
`attend_latent` over a `cache.KVLayout` pool of the latent kind), jitted
here one half-layer at a time as the engine runs a long prompt and then
decodes: chunks of `engine.prefill_chunk_size` rows under a permuted block
table, each attending the pages the chunks before it wrote and its own
rows; then the last `layer_parity.decode_rows` rows again as DECODE STEPS
of `DECODE_LANES` lanes in groups, longest first, every lane reading its
own copy of the pages through its own permuted table beside a decoy lane
whose pages hold other rows. With 8,448 rows that is thirty-three chunks
and sixteen steps of four rows, every decoded row at a context of 8,384
slots or more: nine tiles of 1,024.

The legs: `mhc_coef`, the worst of H_pre's, H_post's and H_res's
90th-percentile row error, both sides from the same bf16 states;
`mhc_mix`, the worse of the two mixes' (h, and X' given the same y), each
side mixing with its own coefficients; `mixer`, the attention's output in
chunks; `decode_mixer`, the same over the decoded rows alone and by their
WORST row, because a fault in a decode step's read (a lane's table, a
page's lookup, a lane's length) shows in a few rows, which a percentile
over 8,448 passes; `ffn_dense`, `ffn_experts` and `routing` as in the other
cuts.

`serve_reference` is what the configuration names as its reference: the
plain reference's log-probs, pushed out of any tolerance (by `FAILED`
nats) where a leg fails its limit, so that the cell's `correct` is decided
by both. The readings are printed where the function runs (the replica's
log); `benchmark/selftest/chip_controls_xing4.py` prints them for the
controls that set the limits.
"""

from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import reference_xing4 as reference
# the 90th-percentile row error, the rows' token ids and the served config
# are any shared-layer cut's; the worst row's and the permuted tables are
# the other latent kind's
from benchmark.parity_glm_5 import _tables, _worst_row_error
from benchmark.parity_mimo_v2 import (
    _row_error,
    parity_tokens,
    program_config,
)
from ray_tpu.models import mla
from ray_tpu.models import xing4 as xg
from ray_tpu.ops.context_attention import CachedContext, causal_rows
from ray_tpu.serve.llm.cache import KVKind, KVLayout

FAILED = 1000.0  # nats taken off every wanted log-prob where a leg fails
READINGS = ("mhc_coef", "mhc_mix", "mixer", "decode_mixer", "ffn_dense",
            "ffn_experts", "routing")
DECODE_LANES = 8  # lanes a decode step: two a group, as the runner makes them


def _layout(cfg, rows: int, page: int, lanes: int) -> KVLayout:
    """A one-layer pool of the latent kind that holds `rows` rows for each
    of `lanes` lanes, behind the null page."""
    return KVLayout.of(KVKind(*cfg.kv_kinds()[0])._replace(layers=1),
                       lanes * -(-rows // page) + 1, page)


@functools.partial(jax.jit, static_argnames=("cfg", "layout", "group"))
def _program_rows(h, p, starts, pool, tables, cfg, layout, group: int = 1):
    """Rows h (B, n, D), lane b's at positions starts[b].. against the
    slots below `starts[b]` of its block table, as a chunk (B = 1, n > 1)
    or a decode step (n = 1, B lanes in groups of `group`) -> (the
    attention's output (B, n, D), the rows' latent rows as the pool takes
    them)."""
    B, n = h.shape[:2]
    at = starts[:, None] + jnp.arange(n)[None]
    ctx = CachedContext.of(layout, pool, pool[..., :0], tables, starts,
                           group)
    own = causal_rows(jnp.ones((B, n), bool))
    return xg._attend_cached(h, p, at, own, ctx, 0, cfg)


def _program_attention(h, p, cfg, chunk: int, page: int, decode_rows: int,
                       seed: int = 0, lanes: int = DECODE_LANES):
    """h (T, D) in the program's dtype -> (the attention's output (T, D),
    the first row that was decoded), as the engine runs a long prompt and
    then decodes: every row goes through the pool in chunks of `chunk`
    under lane 0's block table, a seeded permutation of the pages; then
    the last `decode_rows` rows are run AGAIN as decode steps of `lanes`
    lanes, longest first, and their output is the decode step's. The even
    lanes of a step are at consecutive positions and read their own copy
    of the pages under their own table; beside each runs a decoy, whose
    pages hold the same rows one page on, so that a lane that read by
    another's table, or a slot that was looked up in the wrong page,
    reads other rows."""
    T = h.shape[0]
    layout = _layout(cfg, T, page, lanes)
    tables = _tables(layout, lanes, seed)
    pool = layout.zeros(cfg.dtype)[0]
    out = np.zeros(h.shape, np.float32)
    for s in range(0, T, chunk):
        at = np.arange(s, min(T, s + chunk))
        y, rows = _program_rows(h[None, at], p, jnp.asarray([s], jnp.int32),
                                pool, tables[:1], cfg, layout)
        pool = layout.write(pool, tables[0, at // page], at % page,
                            rows[:, :, None])
        out[at] = np.asarray(y[0], np.float32)
    mine = pool[:, tables[0]]
    pool = pool.at[:, tables[1:].reshape(-1)].set(jnp.concatenate(
        [jnp.roll(mine, lane % 2, axis=1) for lane in range(1, lanes)],
        axis=1))
    per = lanes // 2  # positions a step
    first = T - min(decode_rows, T // 2) // per * per
    for s in range(first, T, per):
        at = np.repeat(np.arange(s + per - 1, s - 1, -1), 2)  # longest first
        y, _ = _program_rows(h[at, None], p, jnp.asarray(at, jnp.int32),
                             pool, tables, cfg, layout,
                             group=max(1, lanes // 4))
        out[at[0::2]] = np.asarray(y[0::2, 0], np.float32)
    return out, first


@functools.partial(jax.jit, static_argnames=("cfg",))
def _program_maps(X, m, cfg):
    """The states X (T, n C) in the program's dtype -> (H_pre (n, T),
    H_post (n, T), H_res (n, n, T), h (T, C)), the program's."""
    pre, post, res = xg.mhc_coefficients(X, m, cfg)
    return pre, post, res, xg.mhc_pre(X, pre, cfg)


@functools.partial(jax.jit, static_argnames=("cfg",))
def _program_post(X, y, post, res, cfg):
    return xg.mhc_post(X, y, post, res, cfg)


@functools.partial(jax.jit, static_argnames=("routed", "cfg"))
def _program_ffn(h, p, routed: bool, cfg):
    """h (T, D) -> (the feed-forward's output, pairs per expert or None)."""
    if routed:
        return mla.experts(h, p, cfg)
    return mla.dense(h, p, cfg), None


@functools.partial(jax.jit, static_argnames=("half", "arch", "dtype"))
def _reference_maps(X, p, half: str, arch: tuple, dtype):
    """The states X (T, n, C) f32 -> (X rounded once to the program's
    dtype, the reference's H_pre, H_post, H_res on it, its h, and the
    half's normed rows rounded once to the program's dtype)."""
    arch = dict(arch)
    p = jax.tree.map(lambda a: a.astype(jnp.float32), p)
    Xb = X.astype(dtype)
    X32 = Xb.astype(jnp.float32)
    pre, post, res = reference.mhc_maps(X32, p["hc_" + half], arch)
    h = reference.mix_pre(X32, pre)
    normed = reference._rmsnorm(h, p[half + "_norm"], arch["rms_norm_eps"])
    return Xb, pre, post, res, h, normed.astype(dtype)


@functools.partial(jax.jit, static_argnames=("half", "routed", "arch",
                                             "operand_dtype"))
def _reference_half(h, p, half: str, routed: bool, arch: tuple,
                    operand_dtype):
    """The half's F on its normed rows h (T, C) -> (y, the experts chosen
    (T, k) or None)."""
    arch = dict(arch)
    p = jax.tree.map(lambda a: a.astype(jnp.float32), p)
    h32 = h.astype(jnp.float32)
    if half == "attn":
        return reference.attention_half(h32, p, arch, operand_dtype), None
    return reference.ffn_half(h32, p, routed, arch, operand_dtype)


@functools.partial(jax.jit, static_argnames=("dtype",))
def _reference_post(Xb, y, post, res, dtype):
    """(X' by the reference's mix on the rounded states and the rounded y,
    float32; y as the program is given it)."""
    yb = y.astype(dtype)
    return reference.mix_post(Xb.astype(jnp.float32),
                              yb.astype(jnp.float32), post, res), yb


def layer_parity(params, tokens, cfg, arch: dict, chunk: int, page: int = 16,
                 decode_rows: int = 64, operand_dtype=jnp.float32,
                 reference_params=None) -> dict:
    """tokens (T,) -> the worst half-layer's reading by leg (`READINGS`).
    The stream goes on along the reference's own answers. A dtype below
    float32, another `arch` and another tree as `reference_params` make
    the REFERENCE's side wrong, for the readings that set the limits."""
    frozen = reference.frozen(arch)
    out = dict.fromkeys(READINGS, 0.0)
    theirs = reference_params or params
    T, n = len(tokens), cfg.hc_mult
    E = cfg.n_routed_experts

    def worst(key, reading):
        out[key] = max(out[key], float(reading))

    with jax.default_matmul_precision("highest"):
        X = reference.enter(theirs, tokens, arch)
    for i, (routed, p, q) in enumerate(zip(
            reference.layers_of(arch), params["layers"], theirs["layers"],
            strict=True)):
        for half in ("attn", "ffn"):
            with jax.default_matmul_precision("highest"):
                Xb, pre, post, res, h, normed = _reference_maps(
                    X, q, half, frozen, cfg.dtype)
            flat = Xb.reshape(T, -1)
            ours_pre, ours_post, ours_res, ours_h = _program_maps(
                flat, p["hc_" + half], cfg)
            for ours, want in ((ours_pre.T, pre), (ours_post.T, post),
                               (ours_res.reshape(n * n, T).T,
                                res.reshape(T, -1))):
                worst("mhc_coef", _row_error(ours, want))
            worst("mhc_mix", _row_error(ours_h, h))
            if half == "attn":
                ours, decoded = _program_attention(
                    normed, p, cfg, chunk, page, decode_rows, seed=i)
                with jax.default_matmul_precision("highest"):
                    y, _ = _reference_half(normed, q, half, False, frozen,
                                           operand_dtype)
                worst("mixer", _row_error(ours, y))
                worst("decode_mixer", _worst_row_error(ours[decoded:],
                                                       y[decoded:]))
            else:
                ours, counts = _program_ffn(normed, p, routed, cfg)
                with jax.default_matmul_precision("highest"):
                    y, chosen = _reference_half(normed, q, half, routed,
                                                frozen, operand_dtype)
                worst("ffn_experts" if routed else "ffn_dense",
                      _row_error(ours, y))
                if routed:
                    theirs_n = np.bincount(np.asarray(chosen).ravel(),
                                           minlength=E)
                    worst("routing", np.abs(theirs_n - np.asarray(counts))
                          .sum() / 2 / T)
            with jax.default_matmul_precision("highest"):
                after, yb = _reference_post(Xb, y, post, res, cfg.dtype)
            worst("mhc_mix", _row_error(
                _program_post(flat, yb, ours_post, ours_res, cfg),
                after.reshape(T, -1)))
            X = after
    return out


def compare(params, cases: list[dict], config: dict, arch=None,
            operand_dtype=jnp.float32, reference_params=None):
    """-> (the reference's log-probs of the cases' tokens, the layer
    parity readings, the limits those are over). The keyword arguments
    compute the reference's side as a control would have it: another
    share, a lower precision, a mechanism left out."""
    arch = arch or reference.published_arch()
    want = reference.serve_reference(
        reference_params or params, None, cases, arch=arch,
        operand_dtype=operand_dtype)
    spec = config["layer_parity"]
    readings = layer_parity(
        params, parity_tokens(cases, spec["rows"]), program_config(config),
        arch, config["engine"]["prefill_chunk_size"],
        config["engine"]["block_size"], spec["decode_rows"], operand_dtype,
        reference_params)
    over = [f"{name} {readings[name]:.4g} over its limit {limit:.4g}"
            for name, limit in spec["limits"].items()
            if not readings[name] <= limit]
    return want, readings, over


def serve_reference(params, model: dict, cases: list[dict]):
    """As `reference_xing4.serve_reference`, and every half-layer held to
    the configuration's `layer_parity` limits."""
    with open(reference._CONFIG) as f:
        config = json.load(f)
    want, readings, over = compare(params, cases, config)
    print("[parity] every half-layer on "
          + str(config["layer_parity"]["rows"]) + " rows: "
          + ", ".join(f"{k} {v:.4g}" for k, v in readings.items())
          + (f"; FAILED: {'; '.join(over)}" if over else "; within limits"),
          flush=True)
    if over:
        want = [[w - FAILED for w in row] for row in want]
    return want
