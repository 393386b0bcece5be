"""Every half-layer of the served ling3 program against the plain
reference's, half-layer by half-layer on the reference's own residual
stream, at the widths and on the weights the engine serves, on at least
8,448 rows.

Why the cell needs it beside the log-prob comparison: the harness's four
check prompts are 24 to 300 tokens, so their log-probs see two chunks of
carried state at most and no latent read past one tile; a KDA state that
drifts (held in fewer bits, a decay misread) shows after thousands of
rows in the channels that forget slowly, not after 300; and, as in the
other shared-layer cuts (PERF.md section 6, PRs 32, 34, 40, 51), the held
experts are a thirty-second of the routed sum. Fed the SAME normed rows,
rounded once to the program's dtype, the two sides see equal inputs, no
difference is carried from one half-layer to the next, and what is left
is rounding.

The program's side is made of the family's own functions
(`ray_tpu.models.kda`: `rows`, `step`, `_inputs`, `_gate`;
`ray_tpu.models.mla`: `attend_cached`, `dense`, `experts`) through the
serve path's own state (`cache.StateLayout` / `StateView`) and pool
(`cache.KVLayout`, `ops/context_attention.py`), jitted here one
half-layer at a time as the engine runs a long prompt and then decodes:

- a **KDA** layer: chunks of `engine.prefill_chunk_size` rows that carry
  S and the conv window in slot `SLOT` of a one-layer state buffer (the
  chunk that ends where the decoded rows begin is a padded one), to the
  last row; then the last `layer_parity.decode_rows` rows AGAIN as decode
  steps of four lanes from the state the chunks had left before them: the
  lane in a slot that is not its lane number, beside two decoys (other
  rows, another state) and a padded lane;
- the **MLA** layer: `parity_xing4._program_attention`, which is written
  on `mla.attend_cached` and a config's `kv_kinds`: chunks under a
  permuted block table, then the last rows as decode steps of eight
  lanes in groups, longest first, beside decoys.

The legs: `kda_gate`, the worse of g's and beta's 90th-percentile row
error; `kda_state`, S in the slot after the chunks against the token
scan's S after the last row (the root mean square over heads of a head's
relative error; the chunks alone, because a decode step's convolution
reads three of its four rows from a window held in bfloat16, which moves
the last rows' k and v by a bfloat16 step and S after them by 2e-3, as
much as a third of what a bfloat16 S reads: the decoded rows are
`decode_mixer`'s); `mixer`, a mixer's output in chunks, either kind;
`decode_mixer`, the same over the decoded rows alone and by their WORST
row; `ffn_dense`, `ffn_experts` and `routing` as in the other cuts. A
reading that is not a number (a state that overflowed on either side: a
program's chunks, a control whose reference has no L2 norm) reads
infinite and is over its limit.

`serve_reference` is what the configuration names as its reference: the
plain reference's log-probs, pushed out of any tolerance (by `FAILED`
nats) where a leg fails its limit, so that the cell's `correct` is decided
by both. The readings are printed where the function runs (the replica's
log); `benchmark/selftest/chip_controls_ling3.py` prints them for the
controls that set the limits.
"""

from __future__ import annotations

import functools
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import reference_ling3 as reference
from benchmark.parity_glm_5 import _worst_row_error
from benchmark.parity_granite_hybrid import _state_error
from benchmark.parity_mimo_v2 import (
    _row_error,
    parity_tokens,
    program_config,
)
from benchmark.parity_xing4 import _program_attention
from ray_tpu.models import kda, mla
from ray_tpu.serve.llm.cache import StateLayout, StateView
from ray_tpu.serve.llm.runner import _next_pow2

FAILED = 1000.0  # nats taken off every wanted log-prob where a leg fails
READINGS = ("kda_gate", "kda_state", "mixer", "decode_mixer", "ffn_dense",
            "ffn_experts", "routing")
SLOTS, SLOT = 8, 2  # the state buffer's slots, and the one the lane owns
# a decode step's lanes, by slot: a decoy, the lane, a padded one, a decoy
STEP_SLOTS = (5, SLOT, -1, 7)
LANE = STEP_SLOTS.index(SLOT)


def _state_layout(cfg) -> StateLayout:
    return StateLayout(1, SLOTS, cfg.state_parts())


@functools.partial(jax.jit, static_argnames=("cfg",))
def _kda_gate(u, p, cfg):
    """u (T, D) -> the program's g (T, H d) and beta (T, H)."""
    _, f, b, _ = kda._inputs(u, p, cfg.kda)
    g, beta = kda._gate(f, b, p, cfg.kda)
    return g.reshape(u.shape[0], -1), beta


@functools.partial(jax.jit, static_argnames=("cfg",))
def _kda_rows(rows, p, buffers, n_valid, fresh, cfg):
    """One chunk's program on the lane's slot -> (out, the buffers)."""
    view = StateView(_state_layout(cfg), buffers, jnp.int32(SLOT), fresh)
    return kda.rows(rows, p, cfg.kda, view, 0, n_valid), view.buffers


@functools.partial(jax.jit, static_argnames=("cfg",))
def _kda_step(rows, p, buffers, cfg):
    """One decode step of `STEP_SLOTS`' lanes -> (out, the buffers)."""
    view = StateView(_state_layout(cfg), buffers,
                     jnp.asarray(STEP_SLOTS, jnp.int32))
    return kda.step(rows, p, cfg.kda, view, 0), view.buffers


def _program_kda(u, p, cfg, chunk: int, decode_rows: int):
    """u (T, D) in the program's dtype -> (the mixer's output (T, D), the
    first row that was decoded, S in the slot after the chunks)."""
    T = u.shape[0]
    first = T - min(decode_rows, T // 2)
    buffers = _state_layout(cfg).zeros()
    out = np.zeros(u.shape, np.float32)
    edges = [*range(0, first, chunk), first, *range(first + chunk, T, chunk),
             T]
    before = None
    for s, e in zip(edges, edges[1:]):
        if s == first:
            before = buffers  # what the chunks left before the decoded rows
        rows = jnp.zeros((_next_pow2(e - s, 16), u.shape[1]), u.dtype) \
            .at[:e - s].set(u[s:e])
        y, buffers = _kda_rows(rows, p, buffers, jnp.int32(e - s),
                               jnp.bool_(s == 0), cfg)
        out[s:e] = np.asarray(y[:e - s], np.float32)
    after_chunks = buffers["s"][0, SLOT]
    # the decoys own the state after the LAST row, the lane the one before
    # the decoded rows, each in its own slot
    buffers = {name: before[name].at[0, jnp.asarray([5, 7])].set(
        buf[0, SLOT]) for name, buf in buffers.items()}
    for t in range(first, T):
        rows = jnp.stack([u[t - 1], u[t], jnp.zeros_like(u[t]), u[t - 2]])
        y, buffers = _kda_step(rows, p, buffers, cfg)
        out[t] = np.asarray(y[LANE], np.float32)
    return out, first, after_chunks


@functools.partial(jax.jit, static_argnames=("routed", "cfg"))
def _program_ffn(h, p, routed: bool, cfg):
    """h (T, D) -> (the feed-forward's output, pairs per expert or None)."""
    if routed:
        return mla.experts(h, p, cfg)
    return mla.dense(h, p, cfg), None


@functools.partial(jax.jit, static_argnames=(
    "kind", "routed", "arch", "operand_dtype", "state_dtype", "dtype"))
def _reference_layer(x, p, kind, routed, arch: tuple, operand_dtype,
                     state_dtype, dtype):
    """The stream x (T, D) f32 -> the reference's two half-layers, each on
    its own normed rows rounded once to the program's dtype: (u, the
    mixer's output, S or None, (g, beta) or None, h, the feed-forward's
    output, the experts chosen or None)."""
    arch = dict(arch)
    p = jax.tree.map(lambda a: a.astype(jnp.float32), p)
    eps = arch["rms_norm_eps"]
    u = reference._rmsnorm(x, p["mixer_norm"], eps).astype(dtype)
    y, state, _, gate = reference.mixer(
        u.astype(jnp.float32), p, kind, arch, operand_dtype, state_dtype)
    h = reference._rmsnorm(x + y, p["ffn_norm"], eps).astype(dtype)
    f, chosen = reference.feed_forward(h.astype(jnp.float32), p, routed,
                                       arch, operand_dtype)
    return u, y, state, gate, h, f, chosen


def layer_parity(params, tokens, cfg, arch: dict, chunk: int, page: int = 16,
                 decode_rows: int = 64, operand_dtype=jnp.float32,
                 state_dtype=jnp.float32, reference_params=None) -> dict:
    """tokens (T,) -> the worst layer's reading by leg (`READINGS`). The
    stream goes on along the reference's own answers. The dtypes below
    float32, another `arch` and another tree as `reference_params` make
    the REFERENCE's side wrong, for the readings that set the limits."""
    frozen = reference.freeze(arch)
    out = dict.fromkeys(READINGS, 0.0)
    theirs = reference_params or params
    T, E = len(tokens), cfg.num_experts

    def worst(key, reading):
        reading = float(reading)
        out[key] = max(out[key], reading if np.isfinite(reading)
                       else float("inf"))

    x = reference.embed(theirs, jnp.asarray(tokens, jnp.int32))
    for i, ((kind, routed), p, q) in enumerate(zip(
            reference.layers_of(arch), params["layers"], theirs["layers"],
            strict=True)):
        with jax.default_matmul_precision("highest"):
            u, y, state, gate, h, f, chosen = _reference_layer(
                x, q, kind, routed, frozen, operand_dtype, state_dtype,
                cfg.dtype)
        if kind == "kda":
            g, beta = _kda_gate(u, p, cfg)
            worst("kda_gate", _row_error(g, gate[0].reshape(T, -1)))
            worst("kda_gate", _row_error(beta, gate[1]))
            ours, decoded, left = _program_kda(u, p, cfg, chunk,
                                               decode_rows)
            worst("kda_state", _state_error(left, state))
        else:
            ours, decoded = _program_attention(
                u, p, cfg, chunk, page, decode_rows, seed=i)
        worst("mixer", _row_error(ours, y))
        worst("decode_mixer", _worst_row_error(ours[decoded:], y[decoded:]))
        ours, counts = _program_ffn(h, p, routed, cfg)
        worst("ffn_experts" if routed else "ffn_dense", _row_error(ours, f))
        if routed:
            theirs_n = np.bincount(np.asarray(chosen).ravel(), minlength=E)
            worst("routing", np.abs(theirs_n - np.asarray(counts)).sum()
                  / 2 / T)
        x = x + y + f
    return out


def compare(params, cases: list[dict], config: dict, arch=None,
            operand_dtype=jnp.float32, state_dtype=jnp.float32,
            reference_params=None):
    """-> (the reference's log-probs of the cases' tokens, the layer
    parity readings, the limits those are over). The keyword arguments
    compute the reference's side as a control would have it: another
    share, a lower precision, a mechanism left out."""
    arch = arch or reference.published_arch()
    want = reference.serve_reference(
        reference_params or params, None, cases, arch=arch,
        operand_dtype=operand_dtype, state_dtype=state_dtype)
    spec = config["layer_parity"]
    readings = layer_parity(
        params, parity_tokens(cases, spec["rows"]), program_config(config),
        arch, config["engine"]["prefill_chunk_size"],
        config["engine"]["block_size"], spec["decode_rows"], operand_dtype,
        state_dtype, reference_params)
    over = [f"{name} {readings[name]:.4g} over its limit {limit:.4g}"
            for name, limit in spec["limits"].items()
            if not readings[name] <= limit]
    return want, readings, over


def serve_reference(params, model: dict, cases: list[dict]):
    """As `reference_ling3.serve_reference`, and every half-layer held to
    the configuration's `layer_parity` limits."""
    with open(reference._CONFIG) as f:
        config = json.load(f)
    began = time.monotonic()
    want, readings, over = compare(params, cases, config)
    print("[parity] every half-layer on "
          + str(config["layer_parity"]["rows"]) + " rows: "
          + ", ".join(f"{k} {v:.4g}" for k, v in readings.items())
          + (f"; FAILED: {'; '.join(over)}" if over else "; within limits")
          + f"; reference and parity took {time.monotonic() - began:.0f} s",
          flush=True)
    if over:
        want = [[w - FAILED for w in row] for row in want]
    return want
