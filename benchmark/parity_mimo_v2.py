"""Every half-layer of the served mimo_v2 program against the plain
reference's, layer by layer on the reference's own hidden states, at the
widths and on the weights the engine serves.

Why the cell needs it beside the log-prob comparison (PERF.md section 6,
PR 32's finding, which holds here more strongly): the held experts are a
sixteenth of the routed sum, so a log-prob at the end of the stack cannot
tell an expert left out, a wrong held offset or a dropped pair from what
bf16 serving legitimately does to a router's choice; and a window layer's
sink or mask is one term among the layers' sums. Fed the SAME normed
rows, rounded once to the program's dtype, the two sides see equal
inputs, no difference is carried from one layer to the next, and what is
left is rounding, a hundredth of a half-layer's output. A mechanism left
out or changed is then tens of times that.

The program's side is made of the family's own layer functions
(`ray_tpu.models.mimo_v2`: `_qkv`, `_sink`, `_project`, `_dense`,
`_experts`) and the serve path's own cached-context attention
(`ops/context_attention.py` `attend_cached` over a `cache.KVLayout` pool of
the layer's kind), jitted here one layer at a time as the engine runs a
long prompt: chunks of `engine.prefill_chunk_size` rows, each attending
to the pages the chunks before it wrote, then the last row as a decode
step. With 641 rows that is two chunks and a half and one step, and a
window layer's table has lost the pages behind its window by then (the
entries are the null page, as the scheduler leaves them). What a program
does around its layers (lanes, pools by kind, padding, sampling) is the
log-prob comparison's to see.

`serve_reference` is what the configuration names as its reference: the
plain reference's log-probs, pushed out of any tolerance (by `FAILED`
nats) where a layer fails its parity limit, so that the cell's `correct`
is decided by both. The readings are printed where the function runs (the
replica's log); `benchmark/selftest/chip_controls_mimo_v2.py` prints them
for the controls that set the limits.
"""

from __future__ import annotations

import dataclasses
import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import reference_mimo_v2 as reference
from benchmark.model_api import load
from ray_tpu.models import mimo_v2 as mm
from ray_tpu.ops.context_attention import (
    CachedContext,
    attend_cached,
    causal_rows,
)
from ray_tpu.serve.llm.cache import KVKind, KVLayout

FAILED = 1000.0  # nats taken off every wanted log-prob where a layer fails
READINGS = ("mixer_full", "mixer_window", "ffn_dense", "ffn_experts",
            "routing")


def program_config(config: dict):
    """The model config the engine serves a configuration file with."""
    cfg = load(config["model"]["config"])()
    return dataclasses.replace(cfg, **config["engine"].get("model_config",
                                                           {}))


@functools.partial(jax.jit, static_argnames=("kind", "cfg", "chunk", "page"))
def _program_attention(h, p, kind: int, cfg, chunk: int, page: int):
    """h (T, D) in the program's dtype -> the attention half's output (T,
    D): rows [0, T - 1) in chunks of `chunk` through a one-layer pool of
    the layer's kind (pages of `page` slots), the last row as a decode
    step."""
    T = h.shape[0]
    layout = KVLayout.of(KVKind(*cfg.kv_kinds()[kind])._replace(layers=1),
                         -(-T // page) + 2, page)
    k_pool, v_pool = layout.zeros(cfg.dtype)
    table = jnp.arange(1, layout.num_blocks)  # logical page j is page j+1
    sink = mm._sink(p, kind, cfg)

    def behind(first_row):
        """The table as the scheduler leaves it for a program whose first
        row is `first_row`: a window kind's pages behind it are gone."""
        if layout.window is None:
            return table
        gone = max(0, first_row - layout.window + 1) // page
        return table.at[:gone].set(0)

    out = []
    for s in range(0, T - 1, chunk):
        e = min(T - 1, s + chunk)
        at = s + jnp.arange(e - s)
        q, k, v = mm._qkv(h[None, s:e], p, at[None], kind, cfg)
        ctx = CachedContext.of(layout, k_pool, v_pool, behind(s)[None],
                               jnp.asarray([s], jnp.int32))
        att = attend_cached(q, k, v, causal_rows(jnp.ones((1, e - s), bool)),
                            ctx, 0, cfg.dtype, sink=sink)
        out.append(mm._project(att, p, cfg)[0])
        k_pool = layout.write(k_pool, table[at // page], at % page, k)
        v_pool = layout.write(v_pool, table[at // page], at % page, v)
    s = T - 1  # one decode step
    q, k, v = mm._qkv(h[s:], p, jnp.asarray([s]), kind, cfg)
    ctx = CachedContext.of(layout, k_pool, v_pool, behind(s)[None],
                           jnp.asarray([s], jnp.int32))
    att = attend_cached(q[:, None], k[:, None], v[:, None],
                        jnp.ones((1, 1, 1), bool), ctx, 0, cfg.dtype,
                        sink=sink)
    out.append(mm._project(att, p, cfg)[:, 0])
    return jnp.concatenate(out)


@functools.partial(jax.jit, static_argnames=("routed", "cfg"))
def _program_ffn(h, p, routed: bool, cfg):
    """h (T, D) -> (the feed-forward half's output, pairs per expert or
    None)."""
    if routed:
        return mm._experts(h, p, cfg)
    return mm._dense(h, p, cfg), None


@functools.partial(jax.jit, static_argnames=(
    "half", "flag", "arch", "operand_dtype", "dtype"))
def _reference_half(x, p, half: str, flag: bool, arch: tuple, operand_dtype,
                    dtype):
    """The stream x (T, D) f32 -> (the half's normed rows rounded once to
    the program's dtype, the reference's half on them, chosen or None).
    `half` "attn": `flag` says window layer; "ffn": routed."""
    arch = dict(arch)
    p = jax.tree.map(lambda a: a.astype(jnp.float32), p)
    h = reference._rmsnorm(x, p[half + "_norm"], arch["layernorm_epsilon"])
    h = h.astype(dtype)
    h32 = h.astype(jnp.float32)
    if half == "attn":
        return h, reference.attention_half(h32, p, flag, arch,
                                           operand_dtype), None
    return (h,) + reference.ffn_half(h32, p, flag, arch, operand_dtype)


def _row_error(got, want):
    """The 90th percentile over rows of |got - want| / |want|: a few rows
    whose routers chose differently do not move it, a fault in every row
    or in one of ten does."""
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    rel = np.linalg.norm(got - want, axis=-1) / np.maximum(
        np.linalg.norm(want, axis=-1), 1e-30)
    return float(np.quantile(rel, 0.9))


def layer_parity(params, tokens, cfg, arch: dict, chunk: int, page: int = 16,
                 operand_dtype=jnp.float32, reference_params=None) -> dict:
    """tokens (T,) -> the worst layer's reading by kind: `mixer_full` /
    `mixer_window` / `ffn_dense` / `ffn_experts` (`_row_error` of the
    half's output) and `routing` (pairs that landed on another expert than
    the reference's, a row, from the pairs per expert). The stream goes on
    along the reference's own answers. A dtype below float32, another
    `arch` and another tree as `reference_params` make the REFERENCE's
    side wrong, for the readings that set the limits."""
    frozen = tuple(sorted(arch.items()))
    out = dict.fromkeys(READINGS, 0.0)
    theirs = reference_params or params
    x = theirs["wte"][jnp.asarray(tokens, jnp.int32)].astype(jnp.float32)
    E = cfg.n_routed_experts
    for (kind, routed), p, q in zip(reference.layers_of(arch),
                                    params["layers"], theirs["layers"],
                                    strict=True):
        with jax.default_matmul_precision("highest"):
            h, want, _ = _reference_half(x, q, "attn", bool(kind), frozen,
                                         operand_dtype, cfg.dtype)
        got = _program_attention(h, p, kind, cfg, chunk, page)
        key = "mixer_window" if kind else "mixer_full"
        out[key] = max(out[key], _row_error(got, want))
        x = x + want
        with jax.default_matmul_precision("highest"):
            h, want, chosen = _reference_half(x, q, "ffn", bool(routed),
                                              frozen, operand_dtype,
                                              cfg.dtype)
        got, counts = _program_ffn(h, p, bool(routed), cfg)
        key = "ffn_experts" if routed else "ffn_dense"
        out[key] = max(out[key], _row_error(got, want))
        if routed:
            theirs_n = np.bincount(np.asarray(chosen).ravel(), minlength=E)
            out["routing"] = max(out["routing"], float(
                np.abs(theirs_n - np.asarray(counts)).sum() / 2
                / len(tokens)))
        x = x + want
    return out


def parity_tokens(cases: list[dict], rows: int) -> list[int]:
    """`rows` token ids for the layer parity: the cases' prompts and
    tokens one after the other, repeated as often as it takes (the check
    requests are shorter than two chunks and a half)."""
    ids = [t for c in cases for t in list(c["prompt"]) + list(c["tokens"])]
    return (ids * (-(-rows // len(ids))))[:rows]


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
    """As `reference_mimo_v2.serve_reference`, and every layer held to
    the configuration's `layer_parity` limits."""
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
