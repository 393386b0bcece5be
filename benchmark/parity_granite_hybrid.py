"""Every mixer and every routed half of the served granite_hybrid program
against the plain reference's, half-layer by half-layer on the
reference's own hidden states, at the widths and on the weights the
engine serves.

Why the cell needs it beside the log-prob comparison
(`benchmark/parity_nemotron_h.py` has the argument at length): a log-prob
at the end of ten layers cannot tell a fault in one half-layer from what
bf16 serving legitimately does to a router's choice among logits that lie
hundredths apart. Fed the SAME normed rows, rounded once to the program's
dtype, the two routers see equal inputs and choose alike, no difference
is carried from one half-layer to the next, and what is left is rounding:
a hundredth of a half-layer's output. A state held in fewer bits, a state
dropped at a chunk's edge, a multiplier left out, an expert left out is
then many times that.

`serve_reference` is what the configuration names as its reference: the
plain reference's log-probs, pushed out of any tolerance (by `FAILED`
nats) where a leg fails its limit, so that the cell's `correct` is decided
by both. The readings are printed where the function runs (the replica's
log); `benchmark/selftest/chip_controls_granite_hybrid.py` prints them for
the controls that set the limits.

The program's side calls the family's own layer functions
(`ray_tpu.models.mamba2`: `rows`, `step`; `ray_tpu.models.granite_hybrid`:
`_qkv`, `_project`, `_experts`), the ones its three serve programs are made of,
jitted here one half-layer at a time. A Mamba mixer runs as the engine
runs a prompt: the first `chunk` rows as a fresh chunk (one chunk of the
chunked form), the rows up to the last as a second chunk that starts from
the state the first left, padded to its bucket (so the padded rows must
leave the state alone), and the last row as a decode step from the state
they left, in one slot of several; the SSM state left in the slot after
it is compared too (`state_ssm`).

What a program does around its layers is the ENGINE's leg to see
(`serve_edge`, `edge_parity`): the harness's four check requests end 44
rows behind a chunk's edge at the nearest, so this leg sends the engine
itself, through `add_request` as every request goes, three prompts at
once that end one and two rows past the first chunk's edge and one row
past the second's: the scheduler's slots, the runner's `fresh` and its
chunk and decode programs at their real sizes. Their log-probs are held
to the reference's (`edge_logprob`), and the SSM state each left in its
slot, compared on the device where it lies, to the reference's at every
Mamba layer (`edge_state`).
"""

from __future__ import annotations

import functools
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import reference_granite_hybrid as reference
# what a parity file asks of any family: the served model config off a
# configuration file, and the two row statistics
from benchmark.parity_lfm2 import (
    _row_error,
    _worst_row_error,
    program_config,
)
from ray_tpu.models import granite_hybrid as gh
from ray_tpu.models import mamba2
from ray_tpu.ops.context_attention import softmax_over
from ray_tpu.serve.llm.cache import StateLayout, StateView
from ray_tpu.serve.llm.runner import _next_pow2

FAILED = 1000.0  # nats taken off every wanted log-prob where a leg fails
READINGS = ("mixer_mamba", "mixer_attention", "ffn_experts", "state_ssm",
            "routing")
EDGE_READINGS = ("edge_logprob", "edge_state")
SLOTS, SLOT = 4, 2  # the decode step's slots, and the one the lane owns
# the engine's leg: prompts of (chunks, rows past the last's edge), served
# at once, and the tokens each streams (the first by its last chunk's
# program, the others by decode steps)
EDGE_PROMPTS = ((1, 1), (1, 2), (2, 1))
EDGE_TOKENS = 2


@functools.partial(jax.jit, static_argnames=("kind", "cfg", "chunk"))
def _program_mixer(u, p, kind: str, cfg, chunk: int):
    """u (T, D) in the program's dtype -> (out (T, D), the SSM state left
    in the slot (H, P, N) or None)."""
    T = u.shape[0]
    if kind == gh.ATTENTION:
        q, k, v = gh._qkv(u[None], p, cfg)
        causal = jnp.tril(jnp.ones((T, T), bool))[None]
        att = softmax_over(q, [(k, v, causal)], 1.0 / cfg.head_dim ** 0.5,
                           cfg.dtype)
        return gh._project(att, p, cfg)[0], None
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
        out.append(mamba2.rows(rows, p, cfg.mamba, view, 0, n)[:n])
        buffers, at = view.buffers, end
    # the last row as a decode step of two lanes, the other a padded one
    step = StateView(layout, buffers, jnp.asarray([-1, SLOT], jnp.int32))
    last = mamba2.step(jnp.stack([u[0], u[T - 1]]), p, cfg.mamba, step, 0)
    return jnp.concatenate(out + [last[1:]]), step.buffers["ssm"][0, SLOT]


@functools.partial(jax.jit, static_argnames=("cfg",))
def _program_ffn(h, p, cfg):
    """h (T, D) in the program's dtype -> (routed + shared (T, D), pairs
    per expert)."""
    return gh._experts(h, p, cfg)


@functools.partial(jax.jit, static_argnames=(
    "kind", "arch", "operand_dtype", "state_dtype", "dtype",
    "drop_state_at"))
def _reference_layer(x, p, kind, arch: tuple, operand_dtype, state_dtype,
                     dtype, drop_state_at, last=None):
    """The stream x (T, D) f32 -> the reference's two half-layers, each
    on its own normed rows rounded once to the program's dtype: (u, the
    mixer's output, the SSM state or None, h, routed + shared, chosen),
    the outputs before the residual multiplier. `drop_state_at`: a
    control, the Mamba mixer computed as if the rows from there on were a
    sequence of their own (what a chunk started from zeros would give).
    `last`: x is padded from that row on, and the state is the one before
    it."""
    arch = dict(arch)
    p = jax.tree.map(lambda a: a.astype(jnp.float32), p)
    eps, r = arch["rms_norm_eps"], arch["residual_multiplier"]
    u = reference._rmsnorm(x, p["input_norm"], eps).astype(dtype)
    u32 = u.astype(jnp.float32)
    if kind == "mamba" and drop_state_at:
        a, _, _ = reference.mixer(u32[:drop_state_at], p, kind, arch,
                                  operand_dtype, state_dtype)
        b, state, _ = reference.mixer(
            u32[drop_state_at:], p, kind, arch, operand_dtype, state_dtype,
            last=None if last is None else last - drop_state_at)
        y = jnp.concatenate([a, b])
    else:
        y, state, _ = reference.mixer(u32, p, kind, arch, operand_dtype,
                                      state_dtype, last=last)
    x = x + r * y
    h = reference._rmsnorm(x, p["post_norm"], eps).astype(dtype)
    f, chosen = reference.feed_forward(h.astype(jnp.float32), p, arch,
                                       operand_dtype)
    return u, y, state, h, f, chosen


def _head_errors(got, want):
    """|got - want| / |want| of each head's state (..., H, P, N) ->
    (..., H), on the device."""
    diff = jnp.sqrt(jnp.sum(jnp.square(got - want), axis=(-2, -1)))
    return diff / jnp.maximum(
        jnp.sqrt(jnp.sum(jnp.square(want), axis=(-2, -1))), 1e-30)


def _state_error(got, want):
    """The root mean square over heads of |got - want| / |want| of a
    head's state (H, P, N): a state held in fewer bits shows in the heads
    that forget slowly, rounding of the rows' products in those that
    forget at once, and the mean over all reads steadier than the worst
    of either."""
    rel = _head_errors(jnp.asarray(got), jnp.asarray(want))
    return float(jnp.sqrt(jnp.mean(rel * rel)))


def layer_parity(params, tokens, cfg, arch: dict, chunk: int,
                 operand_dtype=jnp.float32, state_dtype=jnp.float32,
                 reference_params=None, drop_state_at: int = 0) -> dict:
    """tokens (T,) -> the worst layer's reading by kind: `mixer_mamba`
    (the WORST row's relative error of a Mamba mixer's output),
    `mixer_attention`, `ffn_experts` (`_row_error` of the half-layer's
    output), `state_ssm` (`_state_error` of the SSM state left in the
    slot after the last row), `routing` (pairs that landed on another
    expert than the reference's, a row, from the pairs per expert). The
    stream goes on along the reference's own answers. The dtypes below
    float32, another tree as `reference_params` and `drop_state_at` make
    the REFERENCE's side wrong, for the readings that set the limits."""
    frozen = reference.freeze(arch)
    out = dict.fromkeys(READINGS, 0.0)
    theirs = reference_params or params
    r = arch["residual_multiplier"]
    x = reference.embed(theirs, jnp.asarray(tokens, jnp.int32), arch)
    E = cfg.num_local_experts
    for kind, p, q in zip(reference.kinds_of(arch), params["layers"],
                          theirs["layers"], strict=True):
        with jax.default_matmul_precision("highest"):
            u, want_mix, state, h, want_ffn, chosen = _reference_layer(
                x, q, kind, frozen, operand_dtype, state_dtype, cfg.dtype,
                drop_state_at)
        got_mix, got_state = _program_mixer(u, p, kind, cfg, chunk)
        if kind == gh.MAMBA:
            out["mixer_mamba"] = max(out["mixer_mamba"],
                                     _worst_row_error(got_mix, want_mix))
            out["state_ssm"] = max(out["state_ssm"],
                                   _state_error(got_state, state))
        else:
            out["mixer_attention"] = max(out["mixer_attention"],
                                         _row_error(got_mix, want_mix))
        got_ffn, counts = _program_ffn(h, p, cfg)
        out["ffn_experts"] = max(out["ffn_experts"],
                                 _row_error(got_ffn, want_ffn))
        ours = np.bincount(np.asarray(chosen).ravel(), minlength=E)
        out["routing"] = max(out["routing"], float(
            np.abs(ours - np.asarray(counts)).sum() / 2 / len(tokens)))
        x = x + r * want_mix + r * want_ffn
    return out


def serve_edge(engine, tokens, chunk: int, drive: bool,
               timeout: float = 600.0) -> tuple[list[dict], object]:
    """The engine on EDGE_PROMPTS, all in flight at once, EDGE_TOKENS
    greedy tokens each with their log-probs; the prompts are `tokens`
    turned on by one a prompt and repeated to length. -> (the cases as
    served, the SSM state of every slot once the engine is idle again
    (Mamba layers, slots, H, P, N), left on the device). `drive`: no loop
    thread steps this engine, so this one does."""
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
    return served, engine.runner.state["ssm"]


def edge_parity(params, served: list[dict], slots, arch: dict,
                operand_dtype=jnp.float32, state_dtype=jnp.float32,
                reference_params=None, drop_state_at: int = 0,
                pad_to: int = 64) -> dict:
    """What `serve_edge` got against the reference's whole forward over
    each prompt and the tokens fed after it: `edge_logprob`, the largest
    difference of a streamed token's log-prob in nats; `edge_state`, the
    SSM state the sequence left at every Mamba layer against the slot
    that holds it best (which lane the scheduler gave it is the engine's
    affair; three sequences must be found in three slots), the root mean
    square over layers and heads of a head's relative error, the worst of
    the sequences."""
    frozen = reference.freeze(arch)
    theirs = reference_params or params
    kinds = reference.kinds_of(arch)
    r = arch["residual_multiplier"]
    fed = [list(c["prompt"]) + list(c["tokens"][:-1]) for c in served]
    width = -(-max(map(len, fed)) // pad_to) * pad_to
    out = dict.fromkeys(EDGE_READINGS, 0.0)
    held = []
    for c, seq in zip(served, fed):
        row = np.zeros((width,), np.int32)
        row[:len(seq)] = seq
        with jax.default_matmul_precision("highest"):
            x = reference.embed(theirs, jnp.asarray(row), arch)
            by_slot, layer = 0.0, 0
            for kind, q in zip(kinds, theirs["layers"], strict=True):
                _, y, state, _, f, _ = _reference_layer(
                    x, q, kind, frozen, operand_dtype, state_dtype,
                    jnp.float32, drop_state_at, jnp.int32(len(seq)))
                if state is not None:  # one layer's slots at a time
                    rel = _head_errors(slots[layer], state[None])
                    by_slot = by_slot + jnp.mean(rel * rel, axis=-1)
                    layer += 1
                x = x + r * y + r * f
            logits = reference.head(theirs, x, arch)
        logp = np.asarray(reference.log_softmax(logits, arch["vocab_size"]))
        n = len(c["prompt"])
        out["edge_logprob"] = max(out["edge_logprob"], max(
            abs(got - float(logp[n - 1 + j, t])) for j, (t, got)
            in enumerate(zip(c["tokens"], c["logprobs"]))))
        by_slot = np.sqrt(np.asarray(by_slot) / layer)
        held.append(int(np.argmin(by_slot)))
        out["edge_state"] = max(out["edge_state"], float(by_slot.min()))
    if len(set(held)) != len(held):
        out["edge_state"] = float("inf")  # two sequences, one slot
    return out


def compare(params, cases: list[dict], config: dict, arch=None,
            operand_dtype=jnp.float32, state_dtype=jnp.float32,
            reference_params=None, drop_state_at: int = 0, edge=None):
    """-> (the reference's log-probs of the cases' tokens, the layer
    parity readings on the longest case and, with `edge` (what
    `serve_edge` returned), the engine's leg's, the limits those are
    over). The keyword arguments compute the reference's side as a control
    would have it: another share, a lower precision, other weights than
    the program serves, a chunk's state dropped."""
    arch = arch or reference.published_arch()
    want = reference.serve_reference(
        reference_params or params, None, cases, arch=arch,
        operand_dtype=operand_dtype, state_dtype=state_dtype)
    spec = config["layer_parity"]
    longest = max(cases, key=lambda c: len(c["prompt"]))
    tokens = (list(longest["prompt"]) + list(longest["tokens"]))[:spec["rows"]]
    readings = layer_parity(
        params, tokens, program_config(config), arch,
        config["engine"]["prefill_chunk_size"], operand_dtype, state_dtype,
        reference_params, drop_state_at)
    if edge is not None:
        readings.update(edge_parity(
            params, *edge, arch, operand_dtype, state_dtype,
            reference_params, drop_state_at))
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
    """As `reference_granite_hybrid.serve_reference`, and every half-layer
    held to the configuration's `layer_parity` limits on the longest case,
    and the engine that serves `params` to its leg's."""
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
