"""The nemotron_h stack, plainly: the published forward pass in float32
`jax.numpy`, written from the model's description
(nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16, `model_type` nemotron_h) and
not from `ray_tpu/models/nemotron_h.py`.

No kernels, no cache, no chunked form, no batching, no dispatch: a Python
loop over the layers; the state-space layer as the token-by-token
recurrence; in an expert layer EVERY held expert computed for EVERY token
and combined with the routing weights, which are zero for the experts a
token did not choose. One layer's weights are cast up to float32 at a
time, so that the whole model never exists in float32.

Every block is ``x = x + mixer(rmsnorm(x))`` (eps `layer_norm_epsilon`),
the mixer given by the block's letter in `hybrid_override_pattern`:

  M   z | xBC | dt = h Win                     (no bias; widths d_inner,
          d_inner + 2 G N, heads)
      xBC_t = silu(bias + sum_j w_j xBC_{t-3+j})   (causal depthwise conv
          of width 4; inputs before the sequence are zero)
      x | B | C = xBC                            (heads of P; G groups of N,
          heads h*G/H.. share group g)
      dt = softplus(dt + dt_bias) ; A = -exp(A_log)   (a scalar a head)
      S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T      (S_0 = 0; P x N a head)
      y_t = S_t C_t + D x_t
      y = rmsnorm over each of G groups of (y * silu(z)), times its weight
      out = y Wout
  *   q, k, v = h Wq, h Wk, h Wv (no bias); heads of `head_dim`; causal
      softmax attention, query head i reading K/V head i // (H / HK);
      out = a Wo. NO rotation of q and k: see Departures.
  E   s = sigmoid_float32(h Wrouter) over ALL experts
      the k largest of s + e_score_correction_bias are chosen
      their weights: s at the chosen (without the bias), divided by their
          sum (`norm_topk_prob`), times `routed_scaling_factor`
      out = sum over the HELD experts e of weight[e] * relu(h Wup_e)^2 Wdown_e
            + relu(h Wsup)^2 Wsdown              (the shared expert)
  logits = rmsnorm(x) Whead                      (head not tied)

The share: this chip holds `experts_held` of the router's experts, from
`expert_offset` on. What the absent experts would have added is left out,
here as in the program; the vocabulary is the held slice.

Departures from the published model. One, assumed: q and k are not
rotated. The nemotron_h family applies no position embedding (its Mamba
layers carry order); the catalog's `rope_theta` and
`partial_rotary_factor` are keys its attention does not read. Nothing
else: `n_group` 1 and `topk_group` 1 make the group-limited choice the
plain one. The weights are the program's own pytree (one dict a layer).
Ties among router scores break as `lax.top_k` does (the lower index).

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
                       "configs", "nemotron-3-nano-30b-a3b.json")
ARCH_KEYS = ("hybrid_override_pattern", "num_hidden_layers",
             "mamba_num_heads", "mamba_head_dim", "ssm_state_size",
             "n_groups", "conv_kernel", "num_attention_heads",
             "num_key_value_heads", "head_dim", "num_experts_per_tok",
             "norm_topk_prob", "routed_scaling_factor", "expert_offset",
             "layer_norm_epsilon", "vocab_size")


def published_arch() -> dict:
    """The keys of the published config this reference needs, from the
    benchmark's configuration file."""
    with open(_CONFIG) as f:
        config = json.load(f)
    return {k: config[k] for k in ARCH_KEYS}


def pattern_of(arch: dict) -> str:
    return arch["hybrid_override_pattern"][:arch["num_hidden_layers"]]


def _rmsnorm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * weight


def _relu2(x):
    return jnp.square(jnp.maximum(x, 0.0))


def mamba_mixer(h, p, arch, mm, state_dtype):
    """-> (the mixer's output (T, hidden), the state after the last row
    (H, P, N))."""
    T = h.shape[0]
    H, P = arch["mamba_num_heads"], arch["mamba_head_dim"]
    G, N, K = arch["n_groups"], arch["ssm_state_size"], arch["conv_kernel"]
    d_inner = H * P
    zxbcdt = mm(h, p["in_proj"])
    z = zxbcdt[:, :d_inner]
    xbc = zxbcdt[:, d_inner:d_inner + d_inner + 2 * G * N]
    dt = zxbcdt[:, -H:]
    padded = jnp.concatenate([jnp.zeros((K - 1, xbc.shape[1])), xbc])
    xbc = jax.nn.silu(p["conv_b"] + sum(
        p["conv_w"][j] * padded[j:j + T] for j in range(K)))
    x = xbc[:, :d_inner].reshape(T, H, P)
    B = jnp.repeat(xbc[:, d_inner:d_inner + G * N].reshape(T, G, N),
                   H // G, axis=1)  # (T, H, N): a group's heads share it
    C = jnp.repeat(xbc[:, d_inner + G * N:].reshape(T, G, N), H // G, axis=1)
    dt = jax.nn.softplus(dt + p["dt_bias"])  # (T, H)
    A = -jnp.exp(p["A_log"])  # (H,)

    def step(S, row):
        x_t, B_t, C_t, dt_t = row
        S = jnp.exp(dt_t * A)[:, None, None] * S \
            + (dt_t[:, None] * x_t)[:, :, None] * B_t[:, None, :]
        if state_dtype != jnp.float32:  # (a convert pair XLA would drop)
            kind = jnp.finfo(state_dtype)
            S = jax.lax.reduce_precision(S, kind.nexp, kind.nmant)
        return S, jnp.sum(S * C_t[:, None, :], axis=-1)  # (H, P)

    S, y = jax.lax.scan(step, jnp.zeros((H, P, N)), (x, B, C, dt))
    y = (y + p["D"][:, None] * x).reshape(T, d_inner) * jax.nn.silu(z)
    g = y.reshape(T, G, d_inner // G)
    g = g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True)
                          + arch["layer_norm_epsilon"])
    return mm(g.reshape(T, d_inner) * p["gate_norm"], p["out_proj"]), S


def attention_mixer(h, p, arch, mm, lo):
    T = h.shape[0]
    Hq, HK, D = (arch["num_attention_heads"], arch["num_key_value_heads"],
                 arch["head_dim"])
    q = mm(h, p["wq"]).reshape(T, Hq, D)  # not rotated (assumed)
    k = jnp.repeat(mm(h, p["wk"]).reshape(T, HK, D), Hq // HK, axis=1)
    v = jnp.repeat(mm(h, p["wv"]).reshape(T, HK, D), Hq // HK, axis=1)
    s = jnp.einsum("qhd,khd->hqk", lo(q), lo(k)) / np.sqrt(D)
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -jnp.inf)
    a = jnp.einsum("hqk,khd->qhd", lo(jax.nn.softmax(s, axis=-1)), lo(v))
    return mm(a.reshape(T, Hq * D), p["wo"])


def expert_mixer(h, p, arch, mm, lo, held=None):
    """-> (the held experts' part plus the shared expert, the experts each
    token chose (T, k)). `held` = (offset, count, shared counted or not):
    by default what the weights hold, from the configuration's offset."""
    T = h.shape[0]
    E = p["router"].shape[1]  # the router's width, whatever is held
    offset, count, with_shared = held or (
        arch["expert_offset"], p["we_up"].shape[0], True)
    scores = jax.nn.sigmoid(h @ p["router"])  # float32, all experts
    _, chosen = jax.lax.top_k(scores + p["router_bias"],
                              arch["num_experts_per_tok"])
    top = jnp.take_along_axis(scores, chosen, axis=-1)
    if arch["norm_topk_prob"]:
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    top = top * arch["routed_scaling_factor"]
    weights = jnp.zeros((T, E)).at[jnp.arange(T)[:, None], chosen].set(top)
    weights = weights[:, offset:offset + count]  # zero if unchosen
    up = jnp.einsum("td,edf->etf", lo(h), lo(p["we_up"]))
    out = jnp.einsum("etf,efd->etd", lo(_relu2(up)), lo(p["we_down"]))
    y = jnp.einsum("te,etd->td", weights, out)
    if with_shared:
        y = y + mm(_relu2(mm(h, p["ws_up"])), p["ws_down"])
    return y, chosen


def mixer(h, p, kind: str, arch: dict, operand_dtype=jnp.float32,
          state_dtype=jnp.float32):
    """One block's mixer on its normed rows h (T, hidden), `p` that layer's
    weights in float32. Returns (what the block adds to x, the experts
    each token chose (T, k) or None, the recurrent state after the last
    row or None). `operand_dtype` below float32 rounds every matrix
    product's operands to it first, and `state_dtype` rounds the recurrent
    state after every token: the same mathematics "computed in a lower
    precision", for the readings that set a tolerance; the reference
    itself never uses either."""
    def lo(a):
        return a.astype(operand_dtype).astype(jnp.float32)

    def mm(a, w):
        return lo(a) @ lo(w)

    if kind == "M":
        y, state = mamba_mixer(h, p, arch, mm, state_dtype)
        return y, None, state
    if kind == "*":
        return attention_mixer(h, p, arch, mm, lo), None, None
    if kind == "E":
        y, chosen = expert_mixer(h, p, arch, mm, lo)
        return y, chosen, None
    raise ValueError(f"unknown layer kind {kind!r}")


def layer(x, p, kind: str, arch: dict, operand_dtype=jnp.float32,
          state_dtype=jnp.float32):
    """One block on x (T, hidden), `p` that layer's weights. Returns (x,
    the experts each token chose (T, k), or None)."""
    p = jax.tree.map(lambda a: a.astype(jnp.float32), p)
    h = _rmsnorm(x, p["norm"], arch["layer_norm_epsilon"])
    y, chosen, _ = mixer(h, p, kind, arch, operand_dtype, state_dtype)
    return x + y, chosen


@functools.partial(jax.jit, static_argnames=("kind", "arch", "operand_dtype",
                                             "state_dtype"))
def _layer(x, p, kind, arch: tuple, operand_dtype, state_dtype):
    return layer(x, p, kind, dict(arch), operand_dtype, state_dtype)


def forward(params, tokens, arch: dict, operand_dtype=jnp.float32,
            state_dtype=jnp.float32):
    """tokens (T,) int32 -> (logits (T, padded vocab) float32, the experts
    chosen (expert layers, T, k)). One layer's weights in float32 at a
    time."""
    frozen = tuple(sorted(arch.items()))
    with jax.default_matmul_precision("highest"):
        x = params["wte"][tokens].astype(jnp.float32)
        chosen = []
        for kind, p in zip(pattern_of(arch), params["layers"], strict=True):
            x, c = _layer(x, p, kind, frozen, operand_dtype, state_dtype)
            if c is not None:
                chosen.append(c)
        x = _rmsnorm(x, params["lnf"].astype(jnp.float32),
                     arch["layer_norm_epsilon"])
        logits = x @ params["lm_head"].astype(jnp.float32)
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


def precision_readings(params, cases: list[list[int]], arch: dict,
                       operand_dtype=jnp.float32, state_dtype=jnp.float32,
                       last: int = 8) -> dict:
    """What computing in `operand_dtype` (matrix products) or holding the
    recurrent state in `state_dtype` does to the reference's answers on
    `cases` (token lists): the largest |difference| of the log-prob of the
    reference's own best token over each case's last `last` positions (the
    positions the serve check compares), and the share of (layer, token)
    routing decisions whose chosen experts differ. For PERF.md's record of
    how a tolerance was set; this compares the reference with itself."""
    worst, differ, decisions = 0.0, 0, 0
    for tokens in cases:
        tokens = jnp.asarray(tokens, jnp.int32)
        logits, chosen = forward(params, tokens, arch)
        low_logits, low_chosen = forward(params, tokens, arch, operand_dtype,
                                         state_dtype)
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
