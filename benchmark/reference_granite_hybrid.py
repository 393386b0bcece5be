"""The granite_hybrid stack, plainly: the published forward pass in float32
`jax.numpy`, written from the published implementation
(ibm-granite/granite-4.0-h-small, `model_type` granitemoehybrid;
`transformers` 4.57.6, models/granitemoehybrid/modeling_granitemoehybrid.py:
`GraniteMoeHybridMambaLayer.torch_forward`, `GraniteMoeHybridTopKGating`,
`GraniteMoeHybridMoE`, `GraniteMoeHybridDecoderLayer.forward`, the logits'
scaling in `GraniteMoeHybridForCausalLM.forward`) and not from
`ray_tpu/models/`.

No kernels, no cache, no chunked form, no batching, no dispatch: a Python
loop over the layers; the state-space mixer as the token-by-token
recurrence (a `lax.scan` over rows); EVERY held expert computed for EVERY
token and combined with the routing weights, which are zero for the
experts a token did not choose. One layer's weights are cast up to
float32 at a time, so that the whole model never exists in float32, and
the attention's queries and the feed-forward's rows run in blocks
(`ROW_BLOCK`), so that the cut's widths fit the chip beside the engine.

Every layer, with r = `residual_multiplier` and eps = `rms_norm_eps`:

  x = x + r * mixer(rmsnorm(x, input_norm))
  h = rmsnorm(x, post_norm);  x = x + r * (routed(h) + shared(h))

  mamba      z | xBC | dt = u Win               (no bias; widths d_inner,
                 d_inner + 2 G N, heads)
             xBC_t = silu(bias + sum_j w_j xBC_{t-3+j})   (causal depthwise
                 conv of `mamba_d_conv` 4 rows WITH bias; inputs before the
                 sequence are zero)
             x | B | C = xBC                    (heads of P; G groups of N,
                 G = 1: every head reads the same B and C)
             dt = softplus(dt + dt_bias) ; A = -exp(A_log)  (a scalar a head)
             S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T     (S_0 = 0)
             y_t = S_t C_t + D x_t
             y = rmsnorm over ALL d_inner of (y * silu(z)), times its weight
             out = y Wout
  attention  q, k, v = u Wq, u Wk, u Wv (no bias); heads of `head_dim`;
             NOT rotated; causal softmax of q k^T * `attention_multiplier`,
             query head i reading K/V head i // (H / HK); out = a Wo
  routed     logits = h Wrouter (float32) over ALL experts
             the k largest LOGITS are chosen; their weights: softmax over
                 the chosen k logits alone
             out = sum over the HELD experts e of
                 weight[e] * Wout_e(silu(Wgate_e h) * Wup_e h)
  shared     Wsout(silu(Wsgate h) * Wsup h)     (every row)
  x_0 = Wte[token] * `embedding_multiplier`
  logits = rmsnorm(x, norm) Wte^T / `logits_scaling`        (head tied)

The share: this chip holds the experts its weights carry, from
`expert_offset` on. What the absent experts would have added is left out,
here as in the program; the vocabulary is the held slice.

Departures from the `transformers` file, each a line:
- the fused `input_linear` (2 x width rows an expert, gate then up) is two
  matrices `we_gate`, `we_up` (the shared MLP's likewise): the same
  numbers, `chunk(2)` done at load;
- weights are (in, out), the program's own pytree (one dict a layer),
  where torch's Linear holds (out, in);
- `head_dim` = hidden_size / num_attention_heads = 128: assumed, the
  config has no key for it;
- the clamp of dt to `time_step_limit` (0, inf) is left out: softplus is
  positive, it changes nothing;
- the recurrence runs row by row where `torch_forward` runs its chunked
  form at `mamba_chunk_size`: the same mathematics (tests hold this file
  to that one's logits);
- `held`: `transformers` computes all `num_local_experts`; here the
  experts that are not held add nothing;
- ties among router logits break as `lax.top_k` does (the lower index).

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
                       "configs", "granite-4.0-h-small.json")
ARCH_KEYS = ("layer_types", "num_hidden_layers", "mamba_n_heads",
             "mamba_d_head", "mamba_d_state", "mamba_n_groups",
             "mamba_d_conv", "num_attention_heads", "num_key_value_heads",
             "head_dim", "num_experts_per_tok", "expert_offset",
             "embedding_multiplier", "residual_multiplier",
             "attention_multiplier", "logits_scaling", "rms_norm_eps",
             "vocab_size")
ROW_BLOCK = 512  # rows of attention queries / feed-forward rows at a time


def published_arch() -> dict:
    """The keys of the published config this reference needs, from the
    benchmark's configuration file."""
    with open(_CONFIG) as f:
        config = json.load(f)
    return {k: config[k] for k in ARCH_KEYS}


def arch_of(cfg) -> dict:
    """The same keys off a model config object (`GraniteHybridConfig`):
    how the tests and the CPU rehearsal give the `tiny` preset's
    architecture."""
    return {k: len(cfg.layer_types) if k == "num_hidden_layers"
            else getattr(cfg, k) for k in ARCH_KEYS}


def freeze(arch: dict) -> tuple:
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                        for k, v in arch.items()))


def kinds_of(arch: dict) -> tuple:
    return tuple(arch["layer_types"][:arch["num_hidden_layers"]])


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


def mamba_mixer(u, p, arch, mm, state_dtype, last=None):
    """-> (the mixer's output (T, hidden), the state (H, P, N) after row
    `last` - 1 (the last row by default; `last` may be traced: a sequence
    padded at its end), the conv inputs' K - 1 rows up to there)."""
    T = u.shape[0]
    H, P = arch["mamba_n_heads"], arch["mamba_d_head"]
    G, N, K = (arch["mamba_n_groups"], arch["mamba_d_state"],
               arch["mamba_d_conv"])
    d_inner = H * P
    zxbcdt = mm(u, p["in_proj"])
    z = zxbcdt[:, :d_inner]
    xbc_in = zxbcdt[:, d_inner:d_inner + d_inner + 2 * G * N]
    dt = zxbcdt[:, -H:]
    padded = jnp.concatenate([jnp.zeros((K - 1, xbc_in.shape[1])), xbc_in])
    conv = sum(p["conv_w"][j] * padded[j:j + T] for j in range(K))
    if "conv_b" in p:
        conv = conv + p["conv_b"]
    xbc = jax.nn.silu(conv)
    x = xbc[:, :d_inner].reshape(T, H, P)
    B = jnp.repeat(xbc[:, d_inner:d_inner + G * N].reshape(T, G, N),
                   H // G, axis=1)  # (T, H, N): a group's heads share it
    C = jnp.repeat(xbc[:, d_inner + G * N:].reshape(T, G, N), H // G, axis=1)
    dt = jax.nn.softplus(dt + p["dt_bias"])  # (T, H)
    A = -jnp.exp(p["A_log"])  # (H,)
    counts = jnp.arange(T) < (T if last is None else last)

    def step(S, row):
        x_t, B_t, C_t, dt_t, real = row
        new = jnp.exp(dt_t * A)[:, None, None] * S \
            + (dt_t[:, None] * x_t)[:, :, None] * B_t[:, None, :]
        if state_dtype != jnp.float32:  # (a convert pair XLA would drop)
            kind = jnp.finfo(state_dtype)
            new = jax.lax.reduce_precision(new, kind.nexp, kind.nmant)
        y = jnp.sum(new * C_t[:, None, :], axis=-1)  # (H, P)
        return jnp.where(real, new, S), y

    S, y = jax.lax.scan(step, jnp.zeros((H, P, N)), (x, B, C, dt, counts))
    y = (y + p["D"][:, None] * x).reshape(T, d_inner) * jax.nn.silu(z)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True)
                          + arch["rms_norm_eps"])
    window = padded[T:] if last is None else \
        jax.lax.dynamic_slice_in_dim(padded, last, K - 1)
    return mm(y * p["gate_norm"], p["out_proj"]), S, window


def attention_mixer(u, p, arch, mm, lo):
    T = u.shape[0]
    Hq, HK = arch["num_attention_heads"], arch["num_key_value_heads"]
    D = arch["head_dim"]  # assumed: hidden_size / num_attention_heads
    q = mm(u, p["wq"]).reshape(T, Hq, D)  # not rotated (nope)
    k = jnp.repeat(mm(u, p["wk"]).reshape(T, HK, D), Hq // HK, axis=1)
    v = jnp.repeat(mm(u, p["wv"]).reshape(T, HK, D), Hq // HK, axis=1)

    def rows(qb, at):
        s = jnp.einsum("qhd,khd->hqk", lo(qb), lo(k)) \
            * arch["attention_multiplier"]
        seen = (at + jnp.arange(qb.shape[0]))[:, None] >= jnp.arange(T)[None]
        s = jnp.where(seen, s, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", lo(jax.nn.softmax(s, axis=-1)),
                          lo(v))

    return mm(_in_blocks(rows, q).reshape(T, Hq * D), p["wo"])


def routed_shared(h, p, arch, mm, lo, held=None):
    """-> (the held experts' part plus the shared MLP, the experts each
    token chose (T, k)). `held` = (offset, count, shared counted or not):
    by default what the weights hold, from the configuration's offset."""
    T = h.shape[0]
    E = p["router"].shape[1]  # the router's width, whatever is held
    offset, count, with_shared = held or (
        arch["expert_offset"], p["we_up"].shape[0], True)
    logits = h @ p["router"]  # float32, all experts
    top, chosen = jax.lax.top_k(logits, arch["num_experts_per_tok"])
    top = jax.nn.softmax(top, axis=-1)  # over the chosen alone
    weights = jnp.zeros((T, E)).at[jnp.arange(T)[:, None], chosen].set(top)
    weights = weights[:, offset:offset + count]  # zero if unchosen

    def rows(r, at):
        a = jax.nn.silu(jnp.einsum("td,edf->etf", lo(r), lo(p["we_gate"]))) \
            * jnp.einsum("td,edf->etf", lo(r), lo(p["we_up"]))
        out = jnp.einsum("etf,efd->etd", lo(a), lo(p["we_down"]))
        y = jnp.einsum("te,etd->td", weights[at:at + r.shape[0]], out)
        if with_shared:
            y = y + mm(jax.nn.silu(mm(r, p["ws_gate"])) * mm(r, p["ws_up"]),
                       p["ws_down"])
        return y

    return _in_blocks(rows, h), chosen


def _tools(operand_dtype):
    def lo(a):
        return a.astype(operand_dtype).astype(jnp.float32)

    return (lambda a, w: lo(a) @ lo(w)), lo


def mixer(u, p, kind: str, arch: dict, operand_dtype=jnp.float32,
          state_dtype=jnp.float32, last=None):
    """One layer's mixer on its normed rows u (T, hidden), `p` that
    layer's weights in float32. Returns (what the mixer gives, before the
    residual multiplier; the SSM state or None; the conv window or None).
    `operand_dtype` below float32 rounds every matrix product's operands
    to it first, and `state_dtype` rounds the recurrent state after every
    token: the same mathematics "computed in a lower precision", for the
    readings that set a tolerance; the reference itself never uses
    either."""
    mm, lo = _tools(operand_dtype)
    if kind == "mamba":
        return mamba_mixer(u, p, arch, mm, state_dtype, last)
    if kind == "attention":
        return attention_mixer(u, p, arch, mm, lo), None, None
    raise ValueError(f"unknown layer kind {kind!r}")


def feed_forward(h, p, arch: dict, operand_dtype=jnp.float32, held=None):
    """One layer's second half on its normed rows h (T, hidden): (routed +
    shared, before the residual multiplier; the experts chosen (T, k))."""
    mm, lo = _tools(operand_dtype)
    return routed_shared(h, p, arch, mm, lo, held)


def layer(x, p, kind: str, arch: dict, operand_dtype=jnp.float32,
          state_dtype=jnp.float32):
    """One layer on x (T, hidden), `p` that layer's weights. Returns (x,
    the experts each token chose (T, k))."""
    p = jax.tree.map(lambda a: a.astype(jnp.float32), p)
    eps, r = arch["rms_norm_eps"], arch["residual_multiplier"]
    y, _, _ = mixer(_rmsnorm(x, p["input_norm"], eps), p, kind, arch,
                    operand_dtype, state_dtype)
    x = x + r * y
    y, chosen = feed_forward(_rmsnorm(x, p["post_norm"], eps), p, arch,
                             operand_dtype)
    return x + r * y, chosen


@functools.partial(jax.jit, static_argnames=("kind", "arch", "operand_dtype",
                                             "state_dtype"))
def _layer(x, p, kind, arch: tuple, operand_dtype, state_dtype):
    # one program a KIND of layer (two of them), not one a layer
    return layer(x, p, kind, dict(arch), operand_dtype, state_dtype)


def embed(params, tokens, arch: dict):
    return params["wte"][tokens].astype(jnp.float32) \
        * arch["embedding_multiplier"]


def head(params, x, arch: dict):
    """The last norm, the tied head and the logits' scaling."""
    x = _rmsnorm(x, params["norm"].astype(jnp.float32), arch["rms_norm_eps"])
    return x @ params["wte"].astype(jnp.float32).T / arch["logits_scaling"]


def forward(params, tokens, arch: dict, operand_dtype=jnp.float32,
            state_dtype=jnp.float32):
    """tokens (T,) int32 -> (logits (T, padded vocab) float32, the experts
    chosen (layers, T, k)). One layer's weights in float32 at a time."""
    frozen = freeze(arch)
    with jax.default_matmul_precision("highest"):
        x = embed(params, tokens, arch)
        chosen = []
        for kind, p in zip(kinds_of(arch), params["layers"], strict=True):
            x, c = _layer(x, p, kind, frozen, operand_dtype, state_dtype)
            chosen.append(c)
        logits = head(params, x, arch)
    return logits, jnp.stack(chosen)


def log_softmax(logits, vocab_size: int):
    """Over the real vocabulary: padded rows of the head are masked out."""
    mask = jnp.arange(logits.shape[-1]) < vocab_size
    return jax.nn.log_softmax(jnp.where(mask, logits, -jnp.inf), axis=-1)


def serve_reference(params, model: dict, cases: list[dict],
                    pad_to: int = 64, arch: dict | None = None,
                    operand_dtype=jnp.float32,
                    state_dtype=jnp.float32) -> list[list[float]]:
    """For the serve cells: log p(tokens[i] | prompt + tokens[:i]) of each
    case's streamed tokens, by one full forward pass over the whole
    sequence (teacher forcing; no cache, no state carried in), with the
    very weights the engine serves. `model` carries the harness's five
    sizes; what this family needs beyond them it reads from its
    configuration file. Sequences are padded at the end to a multiple of
    `pad_to`, which a causal model's earlier positions cannot see, so few
    programs serve all. `arch` and the two dtypes are for the controls
    (another share, a lower precision), which must NOT pass the check."""
    arch = arch or published_arch()
    out = []
    for c in cases:
        seq = list(c["prompt"]) + list(c["tokens"])
        width = -(-len(seq) // pad_to) * pad_to
        row = np.zeros((width,), np.int32)
        row[:len(seq)] = seq
        logits, _ = forward(params, jnp.asarray(row), arch, operand_dtype,
                            state_dtype)
        logp = np.asarray(log_softmax(logits, arch["vocab_size"]))
        n = len(c["prompt"])
        out.append([float(logp[n - 1 + j, t])
                    for j, t in enumerate(c["tokens"])])
    return out
