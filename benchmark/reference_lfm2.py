"""The lfm2 stack, plainly: the published forward pass in float32
`jax.numpy`, written from the model's description (LiquidAI/LFM2-8B-A1B,
`model_type` lfm2_moe) and not from `ray_tpu/models/lfm2.py`.

No kernels, no cache, no carried window, no batching, no dispatch: a
Python loop over the layers; the convolution as a sum over a sequence
padded with zeros at its start; in an expert layer EVERY held expert
computed for EVERY token and combined with the routing weights, which are
zero for the experts a token did not choose. One layer's weights are cast
up to float32 at a time, so that the whole model never exists in float32,
and a long sequence's attention and feed-forwards run in blocks of rows
(`ROW_BLOCK`), so that 8,576 rows fit.

Every block is ``h = x + op(rmsnorm(x)); y = h + ffn(rmsnorm(h))`` (eps
`norm_eps`), `op` given by the block's entry in `layer_types`, `ffn` by
`num_dense_layers`:

  conv            B | C | X = u Win                (no bias; hidden each,
                      in that order)
                  g = B * X
                  c_t = sum_{j=0..2} w_j g_{t-2+j}    (depthwise, causal,
                      `conv_L_cache` 3 rows, no bias, no activation; g
                      before the sequence is zero)
                  out = (C * c) Wout
  full_attention  q, k, v = u Wq, u Wk, u Wv (no bias); heads of
                      `head_dim`; q and k RMS-normalised a head (over its
                      `head_dim` values, a learned scale each) BEFORE the
                      rotation; rotary embedding of `rope_theta` over the
                      whole head; causal softmax attention scaled by
                      1 / sqrt(head_dim), query head i reading K/V head
                      i // (H / HK); out = a Wo
  dense ffn       w2(silu(w1 h) * w3 h)            (layers < num_dense_layers)
  expert ffn      s = sigmoid_float32(h Wrouter) over ALL experts
                  the k largest of s + expert_bias are chosen
                  their weights: s at the chosen (without the bias),
                      divided by their sum + 1e-6 (`norm_topk_prob`),
                      times `routed_scaling_factor`
                  out = sum over the HELD experts e of
                      weight[e] * w2_e(silu(w1_e h) * w3_e h); none shared
  logits = rmsnorm(x, embedding_norm) Wte^T

The share: this chip holds the experts its weights carry, from
`expert_offset` on. What the absent experts would have added is left out,
here as in the program; the vocabulary is the held slice.

Departures from the published model, each assumed (the catalog's row has
no key for it):
- assumed: the head is tied to the embedding (the family ties them);
- assumed: `head_dim` = hidden_size / num_attention_heads = 64;
- assumed: the rotation is llama's half-rotation, pairs (i, i + 32).
Ties among router scores break as `lax.top_k` does (the lower index). The
weights are the program's own pytree (one dict a layer).

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
                       "configs", "lfm2-8b-a1b.json")
ARCH_KEYS = ("layer_types", "num_dense_layers", "conv_L_cache",
             "num_attention_heads", "num_key_value_heads", "head_dim",
             "rope_theta", "num_experts_per_tok", "norm_topk_prob",
             "use_expert_bias", "routed_scaling_factor", "expert_offset",
             "norm_eps", "vocab_size")
ROW_BLOCK = 512  # rows of attention queries / feed-forward rows at a time


def published_arch() -> dict:
    """The keys of the published config this reference needs, from the
    benchmark's configuration file."""
    with open(_CONFIG) as f:
        config = json.load(f)
    return {k: config[k] for k in ARCH_KEYS}


def arch_of(cfg) -> dict:
    """The same keys off a model config object (`Lfm2Config`): how the
    tests and the CPU rehearsal give the `tiny` preset's architecture."""
    return {k: getattr(cfg, k) for k in ARCH_KEYS}


def freeze(arch: dict) -> tuple:
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                        for k, v in arch.items()))


def _rmsnorm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * weight


def _in_blocks(fn, rows):
    """fn over `rows` (T, ...) in blocks of ROW_BLOCK rows."""
    T = rows.shape[0]
    if T <= ROW_BLOCK:
        return fn(rows, 0)
    return jnp.concatenate([fn(rows[i:i + ROW_BLOCK], i)
                            for i in range(0, T, ROW_BLOCK)])


def conv_operator(u, p, arch, mm, lo, carried=None, last=None):
    """-> (the operator's output (T, hidden), g's last K - 1 rows).
    `carried` (K - 1, hidden): rows of g from before the sequence, zeros
    by default (the control that drops a chunk's window gives zeros where
    the program carries rows). `last`: the rows of g are the K - 1 up to
    row `last` - 1 (a sequence padded at its end; it may be traced)."""
    T, D = u.shape
    K = arch["conv_L_cache"]
    bcx = mm(u, p["in_proj"])
    B, C, X = bcx[:, :D], bcx[:, D:2 * D], bcx[:, 2 * D:]
    g = B * X
    before = jnp.zeros((K - 1, D)) if carried is None else carried
    padded = jnp.concatenate([before, g])
    c = sum(p["conv_w"][j] * padded[j:j + T] for j in range(K))
    left = padded[T:] if last is None else \
        jax.lax.dynamic_slice_in_dim(padded, last, K - 1)
    return mm(C * c, p["out_proj"]), left


def _rotate(x, positions, theta):
    # assumed: llama's half-rotation over the whole head
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions[:, None, None] * freqs
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x1 * jnp.sin(ang) + x2 * jnp.cos(ang)], axis=-1)


def attention_operator(u, p, arch, mm, lo):
    T = u.shape[0]
    Hq, HK = arch["num_attention_heads"], arch["num_key_value_heads"]
    D = arch["head_dim"]  # assumed: hidden_size / num_attention_heads
    eps, theta = arch["norm_eps"], arch["rope_theta"]
    pos = jnp.arange(T, dtype=jnp.float32)
    q = _rotate(_rmsnorm(mm(u, p["wq"]).reshape(T, Hq, D), p["q_norm"], eps),
                pos, theta)
    k = _rotate(_rmsnorm(mm(u, p["wk"]).reshape(T, HK, D), p["k_norm"], eps),
                pos, theta)
    k = jnp.repeat(k, Hq // HK, axis=1)
    v = jnp.repeat(mm(u, p["wv"]).reshape(T, HK, D), Hq // HK, axis=1)

    def rows(qb, at):
        s = jnp.einsum("qhd,khd->hqk", lo(qb), lo(k)) / np.sqrt(D)
        seen = (at + jnp.arange(qb.shape[0]))[:, None] >= jnp.arange(T)[None]
        s = jnp.where(seen, s, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", lo(jax.nn.softmax(s, axis=-1)),
                          lo(v))

    return mm(_in_blocks(rows, q).reshape(T, Hq * D), p["wo"])


def dense_ffn(h, p, mm):
    return _in_blocks(lambda r, _: mm(
        jax.nn.silu(mm(r, p["w1"])) * mm(r, p["w3"]), p["w2"]), h)


def expert_ffn(h, p, arch, mm, lo):
    """-> (the held experts' part, the experts each token chose (T, k)).
    The experts held are what the weights carry, from the configuration's
    `expert_offset` on."""
    T = h.shape[0]
    E = p["router"].shape[1]  # the router's width, whatever is held
    offset, count = arch["expert_offset"], p["we_up"].shape[0]
    scores = jax.nn.sigmoid(h @ p["router"])  # float32, all experts
    biased = scores + p["expert_bias"] if arch["use_expert_bias"] else scores
    _, chosen = jax.lax.top_k(biased, arch["num_experts_per_tok"])
    top = jnp.take_along_axis(scores, chosen, axis=-1)
    if arch["norm_topk_prob"]:
        top = top / (jnp.sum(top, axis=-1, keepdims=True) + 1e-6)
    top = top * arch["routed_scaling_factor"]
    weights = jnp.zeros((T, E)).at[jnp.arange(T)[:, None], chosen].set(top)
    weights = weights[:, offset:offset + count]  # zero if unchosen

    def rows(r, at):
        a = jax.nn.silu(jnp.einsum("td,edf->etf", lo(r), lo(p["we_gate"]))) \
            * jnp.einsum("td,edf->etf", lo(r), lo(p["we_up"]))
        out = jnp.einsum("etf,efd->etd", lo(a), lo(p["we_down"]))
        return jnp.einsum("te,etd->td", weights[at:at + r.shape[0]], out)

    return _in_blocks(rows, h), chosen


def _tools(operand_dtype):
    def lo(a):
        return a.astype(operand_dtype).astype(jnp.float32)

    return (lambda a, w: lo(a) @ lo(w)), lo


def operator(u, p, kind: str, arch: dict, operand_dtype=jnp.float32,
             carried=None, last=None):
    """One block's operator on its normed rows u (T, hidden), `p` that
    layer's weights in float32. Returns (what the block adds to x, the
    last rows of g (a conv layer) or None). `operand_dtype` below float32
    rounds every matrix product's operands to it first: the same
    mathematics "computed in a lower precision", for the readings that set
    a tolerance; the reference itself never uses it."""
    mm, lo = _tools(operand_dtype)
    if kind == "conv":
        return conv_operator(u, p, arch, mm, lo, carried, last)
    if kind == "full_attention":
        return attention_operator(u, p, arch, mm, lo), None
    raise ValueError(f"unknown layer kind {kind!r}")


def feed_forward(h, p, routed: bool, arch: dict, operand_dtype=jnp.float32):
    """One block's feed-forward on its normed rows h (T, hidden). Returns
    (what the block adds, the experts each token chose (T, k) or None)."""
    mm, lo = _tools(operand_dtype)
    if routed:
        return expert_ffn(h, p, arch, mm, lo)
    return dense_ffn(h, p, mm), None


def kinds_of(arch: dict) -> list[tuple[str, bool]]:
    """(the operator's kind, whether the feed-forward is routed) of every
    block, in order."""
    return [(kind, i >= arch["num_dense_layers"])
            for i, kind in enumerate(arch["layer_types"])]


def layer(x, p, kind: str, routed: bool, arch: dict,
          operand_dtype=jnp.float32):
    """One block on x (T, hidden), `p` that layer's weights. Returns (x,
    the experts each token chose (T, k), or None)."""
    p = jax.tree.map(lambda a: a.astype(jnp.float32), p)
    eps = arch["norm_eps"]
    y, _ = operator(_rmsnorm(x, p["operator_norm"], eps), p, kind, arch,
                    operand_dtype)
    x = x + y
    y, chosen = feed_forward(_rmsnorm(x, p["ffn_norm"], eps), p, routed,
                             arch, operand_dtype)
    return x + y, chosen


@functools.partial(jax.jit, static_argnames=("kind", "routed", "arch",
                                             "operand_dtype"))
def _layer(x, p, kind, routed, arch: tuple, operand_dtype):
    # one program a KIND of block (three of them), not one a layer
    return layer(x, p, kind, routed, dict(arch), operand_dtype)


def forward(params, tokens, arch: dict, operand_dtype=jnp.float32):
    """tokens (T,) int32 -> (logits (T, padded vocab) float32, the experts
    chosen (expert layers, T, k)). One layer's weights in float32 at a
    time."""
    frozen = freeze(arch)
    with jax.default_matmul_precision("highest"):
        x = params["wte"][tokens].astype(jnp.float32)
        chosen = []
        for (kind, routed), p in zip(kinds_of(arch), params["layers"],
                                     strict=True):
            x, c = _layer(x, p, kind, routed, frozen, operand_dtype)
            if c is not None:
                chosen.append(c)
        x = _rmsnorm(x, params["embedding_norm"].astype(jnp.float32),
                     arch["norm_eps"])
        # assumed: the head is the embedding (tied)
        logits = x @ params["wte"].astype(jnp.float32).T
    return logits, jnp.stack(chosen)


def log_softmax(logits, vocab_size: int):
    """Over the real vocabulary: padded rows of the head are masked out."""
    mask = jnp.arange(logits.shape[-1]) < vocab_size
    return jax.nn.log_softmax(jnp.where(mask, logits, -jnp.inf), axis=-1)


def serve_reference(params, model: dict, cases: list[dict],
                    pad_to: int = 64, arch: dict | None = None,
                    operand_dtype=jnp.float32) -> list[list[float]]:
    """For the serve cells: log p(tokens[i] | prompt + tokens[:i]) of each
    case's streamed tokens, by one full forward pass over the whole
    sequence (teacher forcing; no cache, no window carried in), with the
    very weights the engine serves. `model` carries the harness's five
    sizes; what this family needs beyond them it reads from its
    configuration file. Sequences are padded at the end to a multiple of
    `pad_to`, which a causal model's earlier positions cannot see, so few
    programs serve all. `arch` and the dtype are for the controls (another
    share, a lower precision), which must NOT pass the check."""
    arch = arch or published_arch()
    out = []
    for c in cases:
        seq = list(c["prompt"]) + list(c["tokens"])
        width = -(-len(seq) // pad_to) * pad_to
        row = np.zeros((width,), np.int32)
        row[:len(seq)] = seq
        logits, _ = forward(params, jnp.asarray(row), arch, operand_dtype)
        logp = np.asarray(log_softmax(logits, arch["vocab_size"]))
        n = len(c["prompt"])
        out.append([float(logp[n - 1 + j, t])
                    for j, t in enumerate(c["tokens"])])
    return out
