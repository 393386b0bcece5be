"""The OLMoE block, plainly: the published forward pass in float32
`jax.numpy`, written from the model's description (allenai/OLMoE-1B-7B,
`model_type` olmoe) and not from `ray_tpu/models/llama.py`.

No kernels, no KV cache, no scan, no batching, no dispatch: a Python loop
over the layers, and in each layer EVERY expert computed for EVERY token
and combined with the routing weights, which are zero for the experts a
token did not choose. One layer's weights are cast up to float32 at a
time, so that the whole model never exists in float32 (at the published
widths it would not fit beside the engine that serves it).

The block, per layer, on x (T, hidden):

    h = rmsnorm(x) ; q, k, v = h Wq, h Wk, h Wv       (no biases)
    q = rmsnorm_q(q) ; k = rmsnorm_k(k)               (over the whole
        projection, before the heads are split; `clip_qkv` is null)
    split into heads of hidden / n_heads, rotate q and k (rotate-half
        convention, theta 10000), causal softmax attention, x += a Wo
    h = rmsnorm(x) ; p = softmax_float32(h Wrouter) over ALL experts
    keep the k largest p of each token, NOT renormalised
        (`norm_topk_prob` false)
    x += sum over experts e of p[e] * (silu(h Wgate_e) * (h Wup_e)) Wdown_e
    logits = rmsnorm(x) Whead                         (head not tied)

Departures from the published model: none in the mathematics. The weights
are the program's own pytree (blocks stacked along a leading layer axis,
experts along a second; `n_kv_head` equals `n_head` here, and the reference
assumes it). Ties among router probabilities are broken as `lax.top_k`
does (the lower index), as in the system; with continuous random weights
a tie has probability zero.

On a TPU a float32 matmul runs in lower precision unless asked otherwise,
so every entry point runs under `jax.default_matmul_precision("highest")`.
"""

from __future__ import annotations

import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np

_CONFIG = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "configs", "olmoe-1b-7b.json")
ARCH_KEYS = ("num_attention_heads", "num_experts_per_tok", "norm_topk_prob",
             "rms_norm_eps", "rope_theta", "vocab_size")


def published_arch() -> dict:
    """The keys of the published config this reference needs, from the
    benchmark's configuration file."""
    with open(_CONFIG) as f:
        config = json.load(f)
    return {k: config[k] for k in ARCH_KEYS}


def _rmsnorm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * weight


def _rotate(x, theta):
    """x (T, heads, D) at positions 0..T-1: x cos + rotate_half(x) sin."""
    T, _, D = x.shape
    inv_freq = 1.0 / theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    angles = jnp.arange(T, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos = jnp.concatenate([jnp.cos(angles)] * 2, axis=-1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(angles)] * 2, axis=-1)[:, None, :]
    half = jnp.concatenate([-x[..., D // 2:], x[..., :D // 2]], axis=-1)
    return x * cos + half * sin


def layer(x, p, arch: dict, operand_dtype=jnp.float32):
    """One block on x (T, hidden), `p` that layer's weights. Returns
    (x, the experts each token chose (T, k)). `operand_dtype` below
    float32 rounds every matrix product's operands to it first: the same
    mathematics "computed in a lower precision", for the readings that
    set a tolerance; the reference itself never uses it."""
    def lo(a):
        return a.astype(operand_dtype).astype(jnp.float32)

    def mm(a, w):
        return lo(a) @ lo(w)

    p = jax.tree.map(lambda a: a.astype(jnp.float32), p)
    T, hidden = x.shape
    heads, eps = arch["num_attention_heads"], arch["rms_norm_eps"]
    D = hidden // heads
    h = _rmsnorm(x, p["ln_attn"], eps)
    q = _rmsnorm(mm(h, p["wq"]), p["q_norm"], eps).reshape(T, heads, D)
    k = _rmsnorm(mm(h, p["wk"]), p["k_norm"], eps).reshape(T, heads, D)
    v = mm(h, p["wv"]).reshape(T, heads, D)
    q, k = _rotate(q, arch["rope_theta"]), _rotate(k, arch["rope_theta"])
    s = jnp.einsum("qhd,khd->hqk", lo(q), lo(k)) / np.sqrt(D)
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -jnp.inf)
    a = jnp.einsum("hqk,khd->qhd", lo(jax.nn.softmax(s, axis=-1)), lo(v))
    x = x + mm(a.reshape(T, hidden), p["wo"])

    h = _rmsnorm(x, p["ln_mlp"], eps)
    probs = jax.nn.softmax(h @ p["router"], axis=-1)  # float32, all experts
    top, chosen = jax.lax.top_k(probs, arch["num_experts_per_tok"])
    if arch["norm_topk_prob"]:
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    weights = jnp.zeros_like(probs).at[
        jnp.arange(T)[:, None], chosen].set(top)  # (T, E), zero if unchosen
    gate = jnp.einsum("td,edf->etf", lo(h), lo(p["we_gate"]))
    up = jnp.einsum("td,edf->etf", lo(h), lo(p["we_up"]))
    out = jnp.einsum("etf,efd->etd", lo(jax.nn.silu(gate) * up),
                     lo(p["we_down"]))
    return x + jnp.einsum("te,etd->td", weights, out), chosen


@functools.partial(jax.jit, static_argnames=("arch", "operand_dtype"))
def _layer(x, p, arch: tuple, operand_dtype):
    return layer(x, p, dict(arch), operand_dtype)


def forward(params, tokens, arch: dict, operand_dtype=jnp.float32):
    """tokens (T,) int32 -> (logits (T, padded vocab) float32, the experts
    chosen (L, T, k)). One layer's weights in float32 at a time."""
    frozen = tuple(sorted(arch.items()))
    with jax.default_matmul_precision("highest"):
        x = params["wte"][tokens].astype(jnp.float32)
        blocks = params["blocks"]
        chosen = []
        for i in range(blocks["wq"].shape[0]):
            x, c = _layer(x, jax.tree.map(lambda a: a[i], blocks), frozen,
                          operand_dtype)
            chosen.append(c)
        x = _rmsnorm(x, params["lnf"].astype(jnp.float32),
                     arch["rms_norm_eps"])
        logits = x @ params["lm_head"].astype(jnp.float32)
    return logits, jnp.stack(chosen)


def log_softmax(logits, vocab_size: int):
    """Over the real vocabulary: padded rows of the head are masked out."""
    mask = jnp.arange(logits.shape[-1]) < vocab_size
    return jax.nn.log_softmax(jnp.where(mask, logits, -jnp.inf), axis=-1)


def serve_reference(params, model: dict, cases: list[dict],
                    pad_to: int = 64) -> list[list[float]]:
    """For the serve cells: log p(tokens[i] | prompt + tokens[:i]) of each
    case's streamed tokens, by one full forward pass over the whole
    sequence (teacher forcing; no cache), with the very weights the engine
    serves. `model` carries the harness's five sizes; what this family
    needs beyond them it reads from its configuration file. Sequences are
    padded at the end to a multiple of `pad_to`, which a causal model's
    earlier positions cannot see, so few programs serve all."""
    arch = published_arch()
    out = []
    for c in cases:
        seq = list(c["prompt"]) + list(c["tokens"])
        width = -(-len(seq) // pad_to) * pad_to
        row = np.zeros((width,), np.int32)
        row[:len(seq)] = seq
        logits, _ = forward(params, jnp.asarray(row), arch)
        logp = np.asarray(log_softmax(logits, arch["vocab_size"]))
        n = len(c["prompt"])
        out.append([float(logp[n - 1 + j, t])
                    for j, t in enumerate(c["tokens"])])
    return out


def precision_readings(params, cases: list[list[int]], arch: dict,
                       operand_dtype, last: int = 8) -> dict:
    """What computing in `operand_dtype` does to the reference's answers on
    `cases` (token lists): the largest |difference| of the log-prob of the
    reference's own best token over each case's last `last` positions (the
    positions the serve check compares), and the share of (layer, token)
    routing decisions whose chosen experts differ. For PERF.md's record of
    how a tolerance was set; the reference is never given the system's
    routing, this compares the reference with itself."""
    worst, differ, decisions = 0.0, 0, 0
    for tokens in cases:
        tokens = jnp.asarray(tokens, jnp.int32)
        logits, chosen = forward(params, tokens, arch)
        low_logits, low_chosen = forward(params, tokens, arch, operand_dtype)
        logp = log_softmax(logits, arch["vocab_size"])[-last:]
        low = log_softmax(low_logits, arch["vocab_size"])[-last:]
        best = jnp.argmax(logp, axis=-1)[:, None]
        worst = max(worst, float(jnp.max(jnp.abs(
            jnp.take_along_axis(logp, best, 1)
            - jnp.take_along_axis(low, best, 1)))))
        same = jnp.all(jnp.sort(chosen, -1) == jnp.sort(low_chosen, -1), -1)
        differ += int(jnp.sum(~same))
        decisions += same.size
    return {"worst_logprob_diff": worst,
            "routing_differs_share": differ / decisions}

