"""Every mixer of the served nemotron_h program against the plain
reference's, layer by layer on the reference's own hidden states, at the
widths and on the weights the engine serves.

Why the cell needs it beside the log-prob comparison. A log-prob at the
end of 18 blocks cannot tell a fault in the expert layer from what bf16
serving legitimately does: the 6 largest of 128 sigmoid scores lie a few
thousandths apart, bf16 hidden states change the choice in one (layer,
token) of eleven, each change swaps a whole expert's output at a weight
of about 2.5 / 6 and moves the later layers' choices with it, so a sound
run's largest log-prob difference (one swap in one position) is of the
size of every held expert missing from every row, whatever the seeded
distribution: both go with the routed sum's share of the residual stream
(PERF.md section 6, PR 32). Fed the SAME normed rows, rounded once to the
program's dtype, the two routers see equal inputs and choose alike; no
difference is carried from one layer to the next; and what is left is
rounding, a hundredth of a mixer's output. An expert left out, a wrong
offset, scaling factor or selection bias, a dropped pair, a state held
in fewer bits is then tens of times that.

`serve_reference` is what the configuration names as its reference: the
plain reference's log-probs, pushed out of any tolerance (by `FAILED`
nats) where a layer fails its parity limit, so that the cell's `correct`
is decided by both. The readings are printed where the function runs (the
replica's log); `benchmark/selftest/chip_controls_nemotron_h.py` prints
them for the controls that set the limits.

The program's side calls the family's own layer functions
(`ray_tpu.models.nemotron_h`: `_experts`, `_mamba_rows`, `_mamba_step`,
`_qkv`, `_attend`), the ones its three serve programs are made of, jitted
here one layer at a time: all rows but the last as a prompt's rows (the
chunked form, state carried from chunk to chunk), the last as a decode
step from the state they left. What a program does around its layers
(slots, resets, pages, padding) is the log-prob comparison's to see.
"""

from __future__ import annotations

import dataclasses
import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import reference_nemotron_h as reference
from benchmark.model_api import load
from ray_tpu.models import nemotron_h as nh
from ray_tpu.serve.llm.cache import StateLayout, StateView

FAILED = 1000.0  # nats taken off every wanted log-prob where a layer fails


def program_config(config: dict):
    """The model config the engine serves a configuration file with."""
    cfg = load(config["model"]["config"])()
    return dataclasses.replace(cfg, **config["engine"].get("model_config",
                                                           {}))


@functools.partial(jax.jit, static_argnames=("kind", "cfg"))
def _program_mixer(h, p, kind: str, cfg):
    """h (T, D) in the program's dtype -> (out (T, D), pairs per expert
    or None, the SSM state after the last row or None)."""
    T = h.shape[0]
    if kind == "E":
        y, counts = nh._experts(h, p, cfg)
        return y, counts, None
    if kind == "*":
        q, k, v = nh._qkv(h[None], p, cfg)
        causal = jnp.tril(jnp.ones((T, T), bool))[None]
        return nh._attend(q, [(k, v, causal)], p, cfg)[0], None, None
    layout = StateLayout(1, 1, cfg.state_parts())
    rows = StateView(layout, layout.zeros(), jnp.int32(0), fresh=True)
    y = nh._mamba_rows(h[:T - 1], p, cfg, rows, 0, T - 1)
    step = StateView(layout, rows.buffers, jnp.zeros((1,), jnp.int32))
    last = nh._mamba_step(h[T - 1:], p, cfg, step, 0)
    return jnp.concatenate([y, last]), None, step.buffers["ssm"][0, 0]


@functools.partial(jax.jit, static_argnames=(
    "kind", "arch", "operand_dtype", "state_dtype", "dtype"))
def _reference_mixer(x, p, kind, arch: tuple, operand_dtype, state_dtype,
                     dtype):
    """The stream x (T, D) f32 -> (the normed rows rounded once to the
    program's dtype, the reference's mixer on them, chosen, state)."""
    arch = dict(arch)
    p = jax.tree.map(lambda a: a.astype(jnp.float32), p)
    h = reference._rmsnorm(x, p["norm"], arch["layer_norm_epsilon"])
    h = h.astype(dtype)
    return (h,) + reference.mixer(h.astype(jnp.float32), p, kind, arch,
                                  operand_dtype, state_dtype)


def _row_error(got, want):
    """The 90th percentile over rows of |got - want| / |want|: a few rows
    whose routers chose differently do not move it, a fault in every row
    or in one of ten does."""
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    rel = np.linalg.norm(got - want, axis=-1) / np.maximum(
        np.linalg.norm(want, axis=-1), 1e-30)
    return float(np.quantile(rel, 0.9))


def _state_error(got, want):
    """The root mean square over heads of |got - want| / |want| of a
    head's state (H, P, N): a state held in fewer bits shows in the heads
    that forget slowly, rounding of the rows' products in those that
    forget at once, and the mean over all reads steadier than the worst
    of either."""
    got, want = np.asarray(got), np.asarray(want)
    heads = len(want)
    rel = np.linalg.norm((got - want).reshape(heads, -1), axis=-1) \
        / np.maximum(np.linalg.norm(want.reshape(heads, -1), axis=-1), 1e-30)
    return float(np.sqrt(np.mean(rel * rel)))


def layer_parity(params, tokens, cfg, arch: dict,
                 operand_dtype=jnp.float32, state_dtype=jnp.float32,
                 reference_params=None) -> dict:
    """tokens (T,) with T - 1 a whole number of the program's chunks ->
    the worst layer's reading by kind: `mixer_M` / `mixer_E` / `mixer_*`
    (`_row_error` of the mixer's output), `state_M` (`_state_error` of the
    SSM state after the last row), `routing_E` (pairs that landed on
    another expert than the reference's, a row, from the pairs per expert).
    The stream goes on along the
    reference's own answers. The two dtypes below float32, and another
    tree as `reference_params`, make the REFERENCE's side wrong, for the
    readings that set the limits."""
    frozen = tuple(sorted(arch.items()))
    out = {"mixer_M": 0.0, "mixer_E": 0.0, "mixer_*": 0.0, "state_M": 0.0,
           "routing_E": 0.0}
    theirs = reference_params or params
    x = theirs["wte"][jnp.asarray(tokens, jnp.int32)].astype(jnp.float32)
    E = cfg.n_routed_experts
    for kind, p, q in zip(reference.pattern_of(arch), params["layers"],
                          theirs["layers"], strict=True):
        with jax.default_matmul_precision("highest"):
            h, want, chosen, state = _reference_mixer(
                x, q, kind, frozen, operand_dtype, state_dtype, cfg.dtype)
        got, counts, got_state = _program_mixer(h, p, kind, cfg)
        key = f"mixer_{kind}"
        out[key] = max(out[key], _row_error(got, want))
        if kind == "M":
            out["state_M"] = max(out["state_M"],
                                 _state_error(got_state, state))
        if kind == "E":
            ours = np.bincount(np.asarray(chosen).ravel(), minlength=E)
            out["routing_E"] = max(out["routing_E"], float(
                np.abs(ours - np.asarray(counts)).sum() / 2 / len(tokens)))
        x = x + want
    return out


def compare(params, cases: list[dict], config: dict, arch=None,
            operand_dtype=jnp.float32, state_dtype=jnp.float32,
            reference_params=None):
    """-> (the reference's log-probs of the cases' tokens, the layer
    parity readings on the longest case, the limits those are over). The
    keyword arguments compute the reference's side as a control would have
    it: another share, a lower precision, other weights than the program
    serves."""
    arch = arch or reference.published_arch()
    want = reference.serve_reference(
        reference_params or params, None, cases, arch=arch,
        operand_dtype=operand_dtype, state_dtype=state_dtype)
    spec = config["layer_parity"]
    longest = max(cases, key=lambda c: len(c["prompt"]))
    tokens = (list(longest["prompt"]) + list(longest["tokens"]))[:spec["rows"]]
    readings = layer_parity(params, tokens, program_config(config), arch,
                            operand_dtype, state_dtype, reference_params)
    over = [f"{name} {readings[name]:.4g} over its limit {limit:.4g}"
            for name, limit in spec["limits"].items()
            if not readings[name] <= limit]
    return want, readings, over


def serve_reference(params, model: dict, cases: list[dict]):
    """As `reference_nemotron_h.serve_reference`, and every layer held to
    the configuration's `layer_parity` limits on the longest case."""
    with open(reference._CONFIG) as f:
        config = json.load(f)
    want, readings, over = compare(params, cases, config)
    print("[parity] every layer on the longest case: "
          + ", ".join(f"{k} {v:.4g}" for k, v in readings.items())
          + (f"; FAILED: {'; '.join(over)}" if over else "; within limits"),
          flush=True)
    if over:
        want = [[w - FAILED for w in row] for row in want]
    return want
