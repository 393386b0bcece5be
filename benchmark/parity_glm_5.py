"""Every half-layer of the served glm_dsa program against the plain
reference's, layer by layer on the reference's own hidden states, at the
widths and on the weights the engine serves, and the indexer's choice
against the reference's, on at least 3,072 rows.

Why the cell needs it beside the log-prob comparison: the harness's four
check prompts are 24 to 300 tokens, all shorter than the 2,048 slots a row
may attend, so no row of theirs refuses a slot and the log-probs cannot
see the selection at all; and, as in the other shared-layer cuts (PERF.md
section 6, PRs 32 and 34), the held experts are a thirty-second of the
routed sum, which a log-prob at the end of the stack cannot tell from what
bf16 serving does to a router's choice. Fed the SAME normed rows, rounded
once to the program's dtype, the two sides see equal inputs, no difference
is carried from one layer to the next, and what is left is rounding.

The program's side is made of the family's own layer functions
(`ray_tpu.models.glm_dsa`: `_attend_latent`, `_dense`, `_experts`) and
the serve path's own selection inside the cached-context read
(`ops/context_attention.py` `attend_selected` over a `cache.KVLayout` pool
of the latent kind), jitted here one layer at a time as the engine runs a
long prompt and then decodes: chunks of `engine.prefill_chunk_size` rows
under a permuted block table, each choosing among the pages the chunks
before it wrote and its own rows; then the last `DECODE_ROWS` rows again
as DECODE STEPS of `DECODE_LANES` lanes in groups, longest first, every
lane reading its own copy of the pages through its own permuted table
beside a decoy lane whose pages hold other rows. With 3,072 rows that is
twelve chunks and sixty-four steps of four rows, every decoded row at a
context of 2,816 slots or more, a third of them refused.

The legs: `index_select`, the share of a row's chosen slots that are not
the reference's choice on the same rows (mean over the rows; bf16 index
scores swap slots at rank 2,048, a mechanism left out of the indexer
changes the choice wholesale); `mixer`, the attention half's output with
the reference given the PROGRAM's chosen set, so that a slot swapped at
rank 2,048 is not read as an attention error (the 90th percentile over
the rows); `decode_select` and `decode_mixer`, the same two over the
decoded rows alone and by their WORST row, because nearly every decode
step of the cell reads a context past `index_topk` and a fault in that
read (a lane's table, a page's lookup, a lane's length) shows in a few
rows, which a mean or a percentile over 3,072 passes; `ffn_dense`,
`ffn_experts` and `routing` as in the other cuts.

`serve_reference` is what the configuration names as its reference: the
plain reference's log-probs, pushed out of any tolerance (by `FAILED`
nats) where a leg fails its limit, so that the cell's `correct` is decided
by both. The readings are printed where the function runs (the replica's
log); `benchmark/selftest/chip_controls_glm_5.py` prints them for the
controls that set the limits.
"""

from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import reference_glm_5 as reference
# the 90th-percentile row error, the rows' token ids and the served config
# are any shared-layer cut's
from benchmark.parity_mimo_v2 import (
    _row_error,
    parity_tokens,
    program_config,
)
from ray_tpu.models import glm_dsa as gd
from ray_tpu.ops.context_attention import CachedContext, causal_rows
from ray_tpu.serve.llm.cache import KVKind, KVLayout

FAILED = 1000.0  # nats taken off every wanted log-prob where a leg fails
READINGS = ("index_select", "mixer", "decode_select", "decode_mixer",
            "ffn_dense", "ffn_experts", "routing")


DECODE_ROWS = 256  # the last rows of the parity pass, run as decode steps
DECODE_LANES = 8  # lanes a decode step: two a group, as the runner makes them


def _layout(cfg, rows: int, page: int, lanes: int) -> KVLayout:
    """A one-layer pool of the latent kind that holds `rows` rows for each
    of `lanes` lanes, behind the null page."""
    return KVLayout.of(KVKind(*cfg.kv_kinds()[0])._replace(layers=1),
                       lanes * -(-rows // page) + 1, page)


def _tables(layout: KVLayout, lanes: int, seed: int):
    """(lanes, pages a lane) int32: the pool's pages but the null page,
    dealt out to the lanes in a seeded random order, so that no lane's
    logical page j is a physical page anyone could guess."""
    pages = np.random.default_rng(seed).permutation(
        np.arange(1, layout.num_blocks))
    return jnp.asarray(pages.reshape(lanes, -1), jnp.int32)


@functools.partial(jax.jit, static_argnames=("cfg", "layout", "group"))
def _program_rows(h, p, starts, latent_pool, index_pool, tables, cfg, layout,
                  group: int = 1):
    """Rows h (B, n, D), lane b's at positions starts[b].. against the
    slots below `starts[b]` of its block table, as a chunk (B = 1, n > 1)
    or a decode step (n = 1, B lanes in groups of `group`) -> (the
    attention half's output (B, n, D), the rows' choice (B, n, cached
    slots + n), the rows' latent rows and indexer keys as the pools take
    them)."""
    B, n = h.shape[:2]
    at = starts[:, None] + jnp.arange(n)[None]
    ctx = CachedContext.of(layout, latent_pool, index_pool, tables, starts,
                           group)
    own = causal_rows(jnp.ones((B, n), bool))
    y, latent, ki, choice = gd._attend_latent(
        h, p, at, own, ctx, 0, cfg, with_choice=True)
    return y, choice, latent, ki


def _program_attention(h, p, cfg, chunk: int, page: int, seed: int = 0,
                       decode_rows: int = DECODE_ROWS,
                       lanes: int = DECODE_LANES):
    """h (T, D) in the program's dtype -> (the attention half's output (T,
    D), every row's choice (T, T) bool, the first row that was decoded),
    as the engine runs a long prompt and then decodes: every row goes
    through the pool in chunks of `chunk` under lane 0's block table, a
    seeded permutation of the pages; then the last `decode_rows` rows are
    run AGAIN as decode steps of `lanes` lanes, longest first, and their
    output and choice are the decode step's. The even lanes of a step are
    at consecutive positions and read their own copy of the pages under
    their own table; beside each runs a decoy, whose pages hold the same
    rows one page on, so that a lane that read by another's table, or a
    slot that was looked up in the wrong page, reads other rows."""
    T = h.shape[0]
    layout = _layout(cfg, T, page, lanes)
    tables = _tables(layout, lanes, seed)
    pools = list(layout.zeros(cfg.dtype))
    out, chose = np.zeros(h.shape, np.float32), np.zeros((T, T), bool)

    def keep(lane, s, y, choice):
        """Lane `lane`'s rows from position s: their output, and their
        choice among the slots below s and themselves (the table's slots
        from s on hold nothing a row may see, and none may be chosen)."""
        y, choice = np.asarray(y[lane], np.float32), np.asarray(choice[lane])
        n = len(y)
        own = choice.shape[1] - n
        assert not choice[:, s:own].any()
        out[s:s + n] = y
        chose[s:s + n, :s] = choice[:, :s]
        chose[s:s + n, s:s + n] = choice[:, own:]

    for s in range(0, T, chunk):
        at = np.arange(s, min(T, s + chunk))
        y, choice, *rows = _program_rows(
            h[None, at], p, jnp.asarray([s], jnp.int32), *pools, tables[:1],
            cfg, layout)
        pools = [layout.write(pool, tables[0, at // page], at % page,
                              r[:, :, None])
                 for pool, r in zip(pools, rows)]
        keep(0, s, y, choice)
    for i, pool in enumerate(pools):
        mine = pool[:, tables[0]]
        pools[i] = pool.at[:, tables[1:].reshape(-1)].set(jnp.concatenate(
            [jnp.roll(mine, lane % 2, axis=1) for lane in range(1, lanes)],
            axis=1))
    per = lanes // 2  # positions a step
    first = T - min(decode_rows, T // 2) // per * per
    for s in range(first, T, per):
        at = np.repeat(np.arange(s + per - 1, s - 1, -1), 2)  # longest first
        y, choice, *_ = _program_rows(
            h[at, None], p, jnp.asarray(at, jnp.int32), *pools, tables, cfg,
            layout, group=max(1, lanes // 4))
        for lane in range(0, lanes, 2):
            keep(lane, at[lane], y, choice)
    return out, chose, first


@functools.partial(jax.jit, static_argnames=("routed", "cfg"))
def _program_ffn(h, p, routed: bool, cfg):
    """h (T, D) -> (the feed-forward half's output, pairs per expert or
    None)."""
    if routed:
        return gd._experts(h, p, cfg)
    return gd._dense(h, p, cfg), None


@functools.partial(jax.jit, static_argnames=(
    "half", "routed", "arch", "operand_dtype", "dtype"))
def _reference_half(x, p, chosen, half: str, routed: bool, arch: tuple,
                    operand_dtype, dtype):
    """The stream x (T, D) f32 -> (the half's normed rows rounded once to
    the program's dtype, the reference's half on them, and what it chose:
    the indexer's slots (T, T), or the experts (T, k), or None). `half`
    "attn": the attention attends to `chosen`, the program's set."""
    arch = dict(arch)
    p = jax.tree.map(lambda a: a.astype(jnp.float32), p)
    h = reference._rmsnorm(x, p[half + "_norm"], arch["rms_norm_eps"])
    h = h.astype(dtype)
    h32 = h.astype(jnp.float32)
    if half == "attn":
        return (h,) + reference.attention_half(h32, p, arch, operand_dtype,
                                               chosen)
    return (h,) + reference.ffn_half(h32, p, routed, arch, operand_dtype)


def _choice_error(ours, theirs):
    """By row, the share of the two sides' chosen slots that only one of
    them chose: 0 where the choices are equal, 1 where they share
    nothing."""
    ours, theirs = np.asarray(ours), np.asarray(theirs)
    return (ours ^ theirs).sum(-1) / np.maximum(
        ours.sum(-1) + theirs.sum(-1), 1)


def _worst_row_error(got, want):
    """The largest over rows of |got - want| / |want|: one row read wrongly
    moves it."""
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.max(np.linalg.norm(got - want, axis=-1) / np.maximum(
        np.linalg.norm(want, axis=-1), 1e-30)))


def layer_parity(params, tokens, cfg, arch: dict, chunk: int, page: int = 16,
                 operand_dtype=jnp.float32, reference_params=None) -> dict:
    """tokens (T,) -> the worst layer's reading by leg (`READINGS`). The
    stream goes on along the reference's own answers. A dtype below
    float32, another `arch` and another tree as `reference_params` make
    the REFERENCE's side wrong, for the readings that set the limits."""
    frozen = tuple(sorted(arch.items()))
    out = dict.fromkeys(READINGS, 0.0)
    theirs = reference_params or params
    x = theirs["wte"][jnp.asarray(tokens, jnp.int32)].astype(jnp.float32)
    E = cfg.n_routed_experts

    def worst(key, reading):
        out[key] = max(out[key], float(reading))

    for i, (routed, p, q) in enumerate(zip(
            reference.layers_of(arch), params["layers"], theirs["layers"],
            strict=True)):
        h = reference._rmsnorm(
            x, q["attn_norm"].astype(jnp.float32),
            arch["rms_norm_eps"]).astype(cfg.dtype)
        got, chose, decoded = _program_attention(h, p, cfg, chunk, page,
                                                 seed=i)
        with jax.default_matmul_precision("highest"):
            _, want, theirs_chose = _reference_half(
                x, q, jnp.asarray(chose), "attn", False, frozen,
                operand_dtype, cfg.dtype)
        differ = _choice_error(chose, theirs_chose)
        worst("mixer", _row_error(got, want))
        worst("index_select", np.mean(differ))
        worst("decode_mixer", _worst_row_error(got[decoded:], want[decoded:]))
        worst("decode_select", np.max(differ[decoded:]))
        x = x + want
        with jax.default_matmul_precision("highest"):
            h, want, chosen = _reference_half(
                x, q, None, "ffn", routed, frozen, operand_dtype, cfg.dtype)
        got, counts = _program_ffn(h, p, routed, cfg)
        worst("ffn_experts" if routed else "ffn_dense", _row_error(got, want))
        if routed:
            theirs_n = np.bincount(np.asarray(chosen).ravel(), minlength=E)
            worst("routing", np.abs(theirs_n - np.asarray(counts)).sum() / 2
                  / len(tokens))
        x = x + want
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
        config["engine"]["block_size"], operand_dtype, reference_params)
    over = [f"{name} {readings[name]:.4g} over its limit {limit:.4g}"
            for name, limit in spec["limits"].items()
            if not readings[name] <= limit]
    return want, readings, over


def serve_reference(params, model: dict, cases: list[dict]):
    """As `reference_glm_5.serve_reference`, and every layer held to the
    configuration's `layer_parity` limits."""
    with open(reference._CONFIG) as f:
        config = json.load(f)
    want, readings, over = compare(params, cases, config)
    print("[parity] every layer on " + str(config["layer_parity"]["rows"])
          + " rows: " + ", ".join(f"{k} {v:.4g}" for k, v in readings.items())
          + (f"; FAILED: {'; '.join(over)}" if over else "; within limits"),
          flush=True)
    if over:
        want = [[w - FAILED for w in row] for row in want]
    return want
