"""Every operator and every feed-forward of the served lfm2 program
against the plain reference's, half-layer by half-layer on the
reference's own hidden states, at the widths and on the weights the
engine serves.

Why the cell needs it beside the log-prob comparison
(`benchmark/parity_nemotron_h.py` has the argument at length): a log-prob
at the end of 24 blocks cannot tell a fault in an expert layer from what
bf16 serving legitimately does to a router's choice among scores that lie
thousandths apart. Fed the SAME normed rows, rounded once to the program's
dtype, the two routers see equal inputs and choose alike, no difference is
carried from one half-layer to the next, and what is left is rounding: a
hundredth of a half-layer's output. An expert left out, a wrong offset, a
selection made without its bias, a conv window dropped at a chunk's edge
is then tens of times that.

`serve_reference` is what the configuration names as its reference: the
plain reference's log-probs, pushed out of any tolerance (by `FAILED`
nats) where a half-layer fails its parity limit, so that the cell's
`correct` is decided by both. The readings are printed where the function
runs (the replica's log); `benchmark/selftest/chip_controls_lfm2.py`
prints them for the controls that set the limits.

The program's side calls the family's own layer functions
(`ray_tpu.models.lfm2`: `_conv_rows`, `_conv_step`, `_qkv`, `_project`,
`_dense`, `_experts`), the ones its three serve programs are made of,
jitted here one half-layer at a time. A conv operator runs as the engine
runs a prompt: the first `chunk` rows as a fresh chunk, the rows up to the
last as a second chunk that starts from the window the first left, padded
to its bucket (so the padded rows must leave the window alone), and the
last row as a decode step from the window they left, in one slot of
several; the window left in the slot after it is compared too.

What a program does around its layers is the ENGINE's leg to see
(`serve_edge`, `edge_parity`): the harness's four check requests end 44
rows behind a chunk's edge at the nearest, where a window dropped at the
edge moves a log-prob by nothing, so this leg sends the engine itself,
through `add_request` as every request goes, three prompts at once that
end one and two rows past the first chunk's edge and one row past the
second's: the scheduler's slots, the runner's `fresh` and its chunk and
decode programs at their real sizes, chunks of one sequence run between
the others'. Their log-probs (the first comes from the row whose window is
all carried) are held to the reference's (`edge_logprob`), and the rows of
g each left in its slot, read back from the engine's state buffers, to the
reference's at every conv layer (`edge_window`).
"""

from __future__ import annotations

import dataclasses
import functools
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import reference_lfm2 as reference
from benchmark.model_api import load
from ray_tpu.models import lfm2
from ray_tpu.ops.context_attention import softmax_over
from ray_tpu.serve.llm.cache import StateLayout, StateView
from ray_tpu.serve.llm.runner import _next_pow2

FAILED = 1000.0  # nats taken off every wanted log-prob where a layer fails
READINGS = ("op_conv", "op_attention", "ffn_dense", "ffn_experts",
            "window_conv", "routing")
EDGE_READINGS = ("edge_logprob", "edge_window")
SLOTS, SLOT = 4, 2  # the decode step's slots, and the one the lane owns
# the engine's leg: prompts of (chunks, rows past the last's edge), served
# at once, and the tokens each streams (the first by its last chunk's
# program, the others by decode steps)
EDGE_PROMPTS = ((1, 1), (1, 2), (2, 1))
EDGE_TOKENS = 2


def program_config(config: dict):
    """The model config the engine serves a configuration file with."""
    cfg = load(config["model"]["config"])()
    return dataclasses.replace(cfg, **config["engine"].get("model_config",
                                                           {}))


@functools.partial(jax.jit, static_argnames=("kind", "cfg", "chunk"))
def _program_operator(u, p, kind: str, cfg, chunk: int):
    """u (T, D) in the program's dtype -> (out (T, D), the conv window
    left in the slot (K - 1, D) or None)."""
    T = u.shape[0]
    if kind == lfm2.ATTENTION:
        positions = jnp.arange(T)[None]
        q, k, v = lfm2._qkv(u[None], p, positions, cfg)
        causal = jnp.tril(jnp.ones((T, T), bool))[None]
        att = softmax_over(q, [(k, v, causal)], 1.0 / cfg.head_dim ** 0.5,
                           cfg.dtype)
        return lfm2._project(att, p, cfg)[0], None
    layout = StateLayout(1, SLOTS, cfg.state_parts())
    buffers = layout.zeros()
    out, at = [], 0
    for end in (min(chunk, T - 1), T - 1):  # a fresh chunk, a carried one
        if end <= at:
            continue
        n = end - at
        rows = jnp.zeros((_next_pow2(n, 16), u.shape[1]), u.dtype).at[:n].set(
            u[at:end])
        view = StateView(layout, buffers, jnp.int32(SLOT), fresh=at == 0)
        out.append(lfm2._conv_rows(rows, p, cfg, view, 0, n)[:n])
        buffers, at = view.buffers, end
    # the last row as a decode step of two lanes, the other a padded one
    step = StateView(layout, buffers, jnp.asarray([-1, SLOT], jnp.int32))
    last = lfm2._conv_step(jnp.stack([u[0], u[T - 1]]), p, cfg, step, 0)
    window = jnp.stack([step.buffers[f"conv{j}"][0, SLOT]
                        for j in range(cfg.conv_L_cache - 1)])
    return jnp.concatenate(out + [last[1:]]), window


@functools.partial(jax.jit, static_argnames=("routed", "cfg"))
def _program_ffn(h, p, routed: bool, cfg):
    """h (T, D) in the program's dtype -> (out (T, D), pairs per expert
    or None)."""
    if routed:
        return lfm2._experts(h, p, cfg)
    return lfm2._dense(h, p, cfg), None


@functools.partial(jax.jit, static_argnames=(
    "kind", "routed", "arch", "operand_dtype", "dtype", "drop_window_at"))
def _reference_layer(x, p, kind, routed, arch: tuple, operand_dtype, dtype,
                     drop_window_at, last=None):
    """The stream x (T, D) f32 -> the reference's two half-layers, each
    on its own normed rows rounded once to the program's dtype: (u, the
    operator's output, g's last rows or None, h, the feed-forward's
    output, chosen or None). `drop_window_at`: a control, the conv
    computed as if the rows from there on were a sequence of their own
    (what a chunk started from zeros would give). `last`: x is padded
    from that row on, and g's rows are the ones before it."""
    arch = dict(arch)
    p = jax.tree.map(lambda a: a.astype(jnp.float32), p)
    eps = arch["norm_eps"]
    u = reference._rmsnorm(x, p["operator_norm"], eps).astype(dtype)
    u32 = u.astype(jnp.float32)
    if kind == "conv" and drop_window_at:
        a, _ = reference.operator(u32[:drop_window_at], p, kind, arch,
                                  operand_dtype)
        b, window = reference.operator(
            u32[drop_window_at:], p, kind, arch, operand_dtype,
            last=None if last is None else last - drop_window_at)
        y = jnp.concatenate([a, b])
    else:
        y, window = reference.operator(u32, p, kind, arch, operand_dtype,
                                       last=last)
    x = x + y
    h = reference._rmsnorm(x, p["ffn_norm"], eps).astype(dtype)
    f, chosen = reference.feed_forward(h.astype(jnp.float32), p, routed,
                                       arch, operand_dtype)
    return u, y, window, h, f, chosen


def _row_error(got, want):
    """The 90th percentile over rows of |got - want| / |want|: a few rows
    whose routers chose differently do not move it, a fault in every row
    or in one of ten does."""
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    rel = np.linalg.norm(got - want, axis=-1) / np.maximum(
        np.linalg.norm(want, axis=-1), 1e-30)
    return float(np.quantile(rel, 0.9))


def _worst_row_error(got, want):
    """The largest over rows of |got - want| / |want|: a dropped window
    shows in the two rows behind a chunk's edge alone."""
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.max(np.linalg.norm(got - want, axis=-1) / np.maximum(
        np.linalg.norm(want, axis=-1), 1e-30)))


def layer_parity(params, tokens, cfg, arch: dict, chunk: int,
                 operand_dtype=jnp.float32, reference_params=None,
                 drop_window_at: int = 0) -> dict:
    """tokens (T,) -> the worst layer's reading by kind: `op_conv` (the
    WORST row's relative error of a conv operator's output: the rows
    behind a chunk's edge are two of hundreds), `op_attention`,
    `ffn_dense`, `ffn_experts` (`_row_error` of the half-layer's output),
    `window_conv` (the relative error of the rows of g left in the slot
    after the last row), `routing` (pairs that landed on another expert
    than the reference's, a row, from the pairs per expert). The stream
    goes on along the reference's own answers. The dtype below float32,
    another tree as `reference_params` and `drop_window_at` make the
    REFERENCE's side wrong, for the readings that set the limits."""
    frozen = reference.freeze(arch)
    out = dict.fromkeys(READINGS, 0.0)
    theirs = reference_params or params
    x = theirs["wte"][jnp.asarray(tokens, jnp.int32)].astype(jnp.float32)
    E = cfg.num_experts
    for (kind, routed), p, q in zip(reference.kinds_of(arch),
                                    params["layers"], theirs["layers"],
                                    strict=True):
        with jax.default_matmul_precision("highest"):
            u, want_op, window, h, want_ffn, chosen = _reference_layer(
                x, q, kind, routed, frozen, operand_dtype, cfg.dtype,
                drop_window_at)
        got_op, got_window = _program_operator(u, p, kind, cfg, chunk)
        if kind == lfm2.CONV:
            out["op_conv"] = max(out["op_conv"],
                                 _worst_row_error(got_op, want_op))
            out["window_conv"] = max(out["window_conv"],
                                     _worst_row_error(got_window, window))
        else:
            out["op_attention"] = max(out["op_attention"],
                                      _row_error(got_op, want_op))
        got_ffn, counts = _program_ffn(h, p, routed, cfg)
        key = "ffn_experts" if routed else "ffn_dense"
        out[key] = max(out[key], _row_error(got_ffn, want_ffn))
        if routed:
            ours = np.bincount(np.asarray(chosen).ravel(), minlength=E)
            out["routing"] = max(out["routing"], float(
                np.abs(ours - np.asarray(counts)).sum() / 2 / len(tokens)))
        x = x + want_op + want_ffn
    return out


def serve_edge(engine, tokens, chunk: int, drive: bool,
               timeout: float = 600.0) -> tuple[list[dict], np.ndarray]:
    """The engine on EDGE_PROMPTS, all in flight at once, EDGE_TOKENS
    greedy tokens each with their log-probs; the prompts are `tokens`
    turned on by one a prompt and repeated to length. -> (the cases as
    served, the conv rows of every slot once the engine is idle again
    (conv layers, slots, K - 1, D) float32). `drive`: no loop thread
    steps this engine, so this one does."""
    from ray_tpu.serve.llm.config import SamplingParams

    prompts = [np.resize(np.roll(tokens, -i), chunks * chunk + past).tolist()
               for i, (chunks, past) in enumerate(EDGE_PROMPTS)]
    streams = [engine.add_request(p, SamplingParams(
        max_tokens=EDGE_TOKENS, logprobs=True)) for p in prompts]
    deadline = time.monotonic() + timeout
    while any(s.final() is None for s in streams) or engine.has_work():
        if time.monotonic() > deadline:
            raise TimeoutError("the engine's leg of the parity timed out")
        if not (drive and engine.step()):
            time.sleep(0.002)
    served = []
    for p, s in zip(prompts, streams):
        final = s.final()
        if len(final["token_ids"]) != EDGE_TOKENS:
            raise RuntimeError(f"an edge request ended early: {final}")
        served.append({"prompt": p, "tokens": final["token_ids"],
                       "logprobs": final["logprobs"]})
    state = engine.runner.state
    slots = np.stack([np.asarray(state[f"conv{j}"], np.float32)
                      for j in range(len(state))], axis=2)
    return served, slots


def edge_parity(params, served: list[dict], slots, arch: dict,
                operand_dtype=jnp.float32, reference_params=None,
                drop_window_at: int = 0, pad_to: int = 64) -> dict:
    """What `serve_edge` got against the reference's whole forward over
    each prompt and the tokens fed after it: `edge_logprob`, the largest
    difference of a streamed token's log-prob in nats; `edge_window`, the
    rows of g the sequence left at every conv layer against the slot that
    holds them best (which lane the scheduler gave it is the engine's
    affair; three sequences must be found in three slots), `_row_error`
    over layers and rows, the worst of the sequences."""
    frozen = reference.freeze(arch)
    theirs = reference_params or params
    kinds = reference.kinds_of(arch)
    fed = [list(c["prompt"]) + list(c["tokens"][:-1]) for c in served]
    width = -(-max(map(len, fed)) // pad_to) * pad_to
    out = dict.fromkeys(EDGE_READINGS, 0.0)
    held = []
    for c, seq in zip(served, fed):
        row = np.zeros((width,), np.int32)
        row[:len(seq)] = seq
        with jax.default_matmul_precision("highest"):
            x = theirs["wte"][jnp.asarray(row)].astype(jnp.float32)
            windows = []
            for (kind, routed), q in zip(kinds, theirs["layers"],
                                         strict=True):
                _, y, window, _, f, _ = _reference_layer(
                    x, q, kind, routed, frozen, operand_dtype, jnp.float32,
                    drop_window_at, jnp.int32(len(seq)))
                if window is not None:
                    windows.append(window)
                x = x + y + f
            x = reference._rmsnorm(
                x, theirs["embedding_norm"].astype(jnp.float32),
                arch["norm_eps"])
            logits = x @ theirs["wte"].astype(jnp.float32).T
        logp = np.asarray(reference.log_softmax(logits, arch["vocab_size"]))
        n = len(c["prompt"])
        out["edge_logprob"] = max(out["edge_logprob"], max(
            abs(got - float(logp[n - 1 + j, t])) for j, (t, got)
            in enumerate(zip(c["tokens"], c["logprobs"]))))
        want = np.asarray(jnp.stack(windows)).reshape(-1, slots.shape[-1])
        by_slot = [_row_error(slots[:, s].reshape(want.shape), want)
                   for s in range(slots.shape[1])]
        held.append(int(np.argmin(by_slot)))
        out["edge_window"] = max(out["edge_window"], min(by_slot))
    if len(set(held)) != len(held):
        out["edge_window"] = float("inf")  # two sequences, one slot
    return out


def compare(params, cases: list[dict], config: dict, arch=None,
            operand_dtype=jnp.float32, reference_params=None,
            drop_window_at: int = 0, edge=None):
    """-> (the reference's log-probs of the cases' tokens, the layer
    parity readings on the longest case and, with `edge` (what
    `serve_edge` returned), the engine's leg's, the limits those are
    over). The keyword arguments compute the reference's side as a control
    would have it: another share, a lower precision, other weights than
    the program serves, a chunk's window dropped."""
    arch = arch or reference.published_arch()
    want = reference.serve_reference(
        reference_params or params, None, cases, arch=arch,
        operand_dtype=operand_dtype)
    spec = config["layer_parity"]
    longest = max(cases, key=lambda c: len(c["prompt"]))
    tokens = (list(longest["prompt"]) + list(longest["tokens"]))[:spec["rows"]]
    readings = layer_parity(
        params, tokens, program_config(config), arch,
        config["engine"]["prefill_chunk_size"], operand_dtype,
        reference_params, drop_window_at)
    if edge is not None:
        readings.update(edge_parity(params, *edge, arch, operand_dtype,
                                    reference_params, drop_window_at))
    over = [f"{name} {readings[name]:.4g} over its limit {limit:.4g}"
            for name, limit in spec["limits"].items()
            if not readings[name] <= limit]
    return want, readings, over


def serve_edge_beside(params, cases: list[dict], chunk: int):
    """`serve_edge` by the engine that serves `params` in this process
    (the replica's, whose loop thread steps it), on the longest case's
    tokens."""
    from ray_tpu.serve.llm import engine as llm_engine

    engine, = (e for e in llm_engine.engines() if e.runner.params is params)
    longest = max(cases, key=lambda c: len(c["prompt"]))
    return serve_edge(
        engine, np.asarray(list(longest["prompt"]) + list(longest["tokens"])),
        chunk, drive=False)


def serve_reference(params, model: dict, cases: list[dict]):
    """As `reference_lfm2.serve_reference`, and every half-layer held to
    the configuration's `layer_parity` limits on the longest case, and
    the engine that serves `params` to its leg's."""
    with open(reference._CONFIG) as f:
        config = json.load(f)
    edge = serve_edge_beside(params, cases,
                             config["engine"]["prefill_chunk_size"])
    want, readings, over = compare(params, cases, config, edge=edge)
    print("[parity] every half-layer on the longest case, and the engine "
          "past a chunk's edge: "
          + ", ".join(f"{k} {v:.4g}" for k, v in readings.items())
          + (f"; FAILED: {'; '.join(over)}" if over else "; within limits"),
          flush=True)
    if over:
        want = [[w - FAILED for w in row] for row in want]
    return want
