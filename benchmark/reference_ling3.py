"""The ling3 stack (Ling-3.0-flash's language model), plainly: the forward
pass in float32 `jax.numpy`, written from the published descriptions (Kimi
Linear, arXiv:2510.26692, section 3, for the KDA mixer; DeepSeek-V3 as
`transformers` 4.57.6 models/deepseek_v3 has it for latent attention and
the `noaux_tc` router; the keys of inclusionAI/Ling-3.0-flash-VL's
config.json) and not from `ray_tpu/models/`: nothing of `models/kda.py`,
`models/mla.py` or `models/moe.py` is imported.

No kernels, no cache, no chunked form, no batching, no dispatch: a Python
loop over the layers; the KDA recurrence TOKEN BY TOKEN (a `lax.scan` over
single rows, the equation below as it stands); latent attention
up-projected (a K and a V head from every latent row); EVERY held expert
computed for EVERY token and combined with the routing weights, which are
zero for the experts a token did not choose. One layer's weights are cast
up to float32 at a time, and attention's queries and the feed-forward's
rows run in blocks (`ROW_BLOCK`), so that the cut's widths fit the chip
beside the engine.

Every layer, eps = `rms_norm_eps`:

  x = x + mixer(rmsnorm(x, mixer_norm));  x = x + ffn(rmsnorm(x, ffn_norm))

  kda   q~ | k~ | v~ | f | b | o = u W_in        (widths 3 x H d, H d, H, H)
        q~, k~, v_t = silu(sum_j w_j (.)_{t-3+j})   (causal depthwise conv of
            `short_conv_kernel_size` 4 rows, no bias; inputs before the
            sequence are zero)
        q = q~ / sqrt(|q~|^2 + 1e-6) / sqrt(d);  k = k~ / sqrt(|k~|^2 + 1e-6)
        g = `kda_lower_bound` * sigmoid(exp(A_log)_head * (f + dt_bias))
        beta = sigmoid(b)                           (a scalar a head)
        S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T
        o_t = S_t^T q_t                             (S_0 = 0, float32)
        y = [sigmoid(o)_head * rmsnorm_head(o_t) * o_norm] W_out
  mla   q = u W_q (H heads of nope | rope; NO low-rank pair, no query norm)
        c | k_pe = u W_kva;  c = rmsnorm(c, kv_norm)
        the rope lanes of q and k_pe rotated, interleaved pairs (2i, 2i+1)
            at `rope_theta`^(-2i / rope), no scaling
        K head = [c W_kb | k_pe],  V head = c W_vb
        causal softmax of q K^T / sqrt(nope + rope);  out = a W_o
  dense W_down(silu(W_gate h) * W_up h)             (layers < first_k_dense)
  routed scores = sigmoid(h W_router) (float32), biased = scores + bias
        the experts are `n_group` groups of neighbours; a group's score is
            the sum of its two largest biased scores; the `topk_group`
            best groups stay, and the k largest biased scores among their
            experts are chosen
        weights = the chosen UNBIASED scores / their sum * scaling factor
        out = sum over the HELD experts e of weight[e] * SwiGLU_e(h)
            + SwiGLU_shared(h)
  logits = rmsnorm(x, lnf) W_head

The share: this chip holds the experts its weights carry, from
`expert_offset` on. What the absent experts would have added is left out,
here as in the program; the vocabulary is the held slice.

Departures, each a line: weights are (in, out) in the program's own pytree
(`in_proj` the six projections side by side, `wkv_b` as its two column
groups); ties among router scores break as `lax.top_k` does; masked
experts are -inf where `transformers` writes 0.0 (a biased sigmoid score
is positive: the same choice); the program holds a lane's conv window
(the last three q~ | k~ | v~ rows of its earlier programs) rounded to
its dtype, this file rounds nothing.

`arch["leave_out"]` (absent in the published architecture) names
mechanisms the CONTROLS compute wrongly on purpose, so that a limit can
be shown to see them: "safe_gate" (the gate's other reading, ``g =
max(-exp(A_log) softplus(f + dt_bias), lower_bound)``), "beta" (1), "l2",
"conv", "group_limit". The reference itself never sets it.

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
                       "configs", "ling-3.0-flash-vl.json")
ARCH_KEYS = ("layer_types", "num_hidden_layers", "first_k_dense_replace",
             "num_attention_heads", "head_dim", "short_conv_kernel_size",
             "kda_lower_bound", "kv_lora_rank", "qk_nope_head_dim",
             "qk_rope_head_dim", "v_head_dim", "rope_theta",
             "num_experts_per_tok", "n_group", "topk_group",
             "norm_topk_prob", "routed_scaling_factor", "expert_offset",
             "rms_norm_eps", "vocab_size")
ROW_BLOCK = 512  # rows of attention queries / feed-forward rows at a time
L2_EPS = 1e-6


def published_arch() -> dict:
    """The keys of the published config this reference needs, from the
    benchmark's configuration file."""
    with open(_CONFIG) as f:
        config = json.load(f)
    return {k: config[k] for k in ARCH_KEYS}


def arch_of(cfg) -> dict:
    """The same keys off a model config object (`Ling3Config`): how the
    tests and the CPU rehearsal give the `tiny` preset's architecture."""
    return {k: list(cfg.kinds) if k == "layer_types" else getattr(cfg, k)
            for k in ARCH_KEYS}


def freeze(arch: dict) -> tuple:
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                        for k, v in arch.items()))


def kinds_of(arch: dict) -> tuple:
    return tuple(arch["layer_types"][:arch["num_hidden_layers"]])


def _rmsnorm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * weight


def _in_blocks(fn, rows):
    """``fn(block, the block's first row)`` over `rows` (T, ...) in blocks
    of ROW_BLOCK rows, one after the other (`lax.map`: one block's program
    however many blocks, where a Python loop wrote 17 copies of it at
    8,448 rows, 12-19 MB an executable in a compile cache of 192 MB: my
    chip runs, PR 61). The last block is padded with zero rows, whose
    results are dropped."""
    T = rows.shape[0]
    if T <= ROW_BLOCK:
        return fn(rows, 0)
    n = -(-T // ROW_BLOCK)
    padded = jnp.pad(rows, ((0, n * ROW_BLOCK - T),)
                     + ((0, 0),) * (rows.ndim - 1))
    out = jax.lax.map(
        lambda at: fn(jax.lax.dynamic_slice_in_dim(padded, at, ROW_BLOCK),
                      at), jnp.arange(n) * ROW_BLOCK)
    return out.reshape(n * ROW_BLOCK, *out.shape[2:])[:T]


def kda_gate(f, b, p, arch):
    """The gate's logits f (T, H d), b (T, H) -> (g (T, H, d), beta (T,
    H))."""
    H, d = arch["num_attention_heads"], arch["head_dim"]
    out = arch.get("leave_out", ())
    rate = jnp.exp(p["A_log"])[:, None]
    f = (f + p["dt_bias"]).reshape(-1, H, d)
    if "safe_gate" in out:  # the reading NOT taken
        g = jnp.maximum(-rate * jax.nn.softplus(f), arch["kda_lower_bound"])
    else:
        g = arch["kda_lower_bound"] * jax.nn.sigmoid(rate * f)
    beta = jnp.ones_like(b) if "beta" in out else jax.nn.sigmoid(b)
    return g, beta


def kda_mixer(u, p, arch, mm, state_dtype, last=None):
    """-> (the mixer's output (T, hidden), the state (H, d, d) after row
    `last` - 1 (the last row by default), the conv inputs' K - 1 rows up
    to there, (g, beta))."""
    T = u.shape[0]
    H, d, K = (arch["num_attention_heads"], arch["head_dim"],
               arch["short_conv_kernel_size"])
    out = arch.get("leave_out", ())
    C = 3 * H * d
    proj = mm(u, p["in_proj"])
    qkv_in, f = proj[:, :C], proj[:, C:C + H * d]
    b, o_gate = proj[:, C + H * d:C + H * d + H], proj[:, C + H * d + H:]
    padded = jnp.concatenate([jnp.zeros((K - 1, C)), qkv_in])
    conv = qkv_in if "conv" in out else sum(
        p["conv_w"][j] * padded[j:j + T] for j in range(K))
    x = jax.nn.silu(conv).reshape(T, 3, H, d)
    q, k, v = x[:, 0], x[:, 1], x[:, 2]
    if "l2" not in out:
        q = q / jnp.sqrt(jnp.sum(q * q, -1, keepdims=True) + L2_EPS)
        k = k / jnp.sqrt(jnp.sum(k * k, -1, keepdims=True) + L2_EPS)
    q = q * d ** -0.5
    g, beta = kda_gate(f, b, p, arch)
    counts = jnp.arange(T) < (T if last is None else last)

    def step(S, row):
        q_t, k_t, v_t, g_t, b_t, real = row
        decayed = jnp.exp(g_t)[:, :, None] * S  # Diag(exp(g)) S
        new = decayed + b_t[:, None, None] * k_t[:, :, None] * (
            v_t - jnp.einsum("hc,hcv->hv", k_t, decayed))[:, None, :]
        if state_dtype != jnp.float32:  # (a convert pair XLA would drop)
            kind = jnp.finfo(state_dtype)
            new = jax.lax.reduce_precision(new, kind.nexp, kind.nmant)
        return jnp.where(real, new, S), jnp.einsum("hc,hcv->hv", q_t, new)

    S, o = jax.lax.scan(step, jnp.zeros((H, d, d)),
                        (q, k, v, g, beta, counts))
    o = _rmsnorm(o, p["o_norm"], arch["rms_norm_eps"]) \
        * jax.nn.sigmoid(o_gate)[:, :, None]
    window = padded[T:] if last is None else \
        jax.lax.dynamic_slice_in_dim(padded, last, K - 1)
    return mm(o.reshape(T, H * d), p["out_proj"]), S, window, (g, beta)


def _rotate(x, positions, arch):
    """The lanes of x (T, [H,] rope) rotated, interleaved pairs."""
    width = arch["qk_rope_head_dim"]
    freqs = arch["rope_theta"] ** (-np.arange(0, width, 2) / width)
    angles = positions[:, None] * jnp.asarray(freqs, jnp.float32)
    if x.ndim == 3:
        angles = angles[:, None]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos], -1) \
        .reshape(x.shape)


def mla_mixer(u, p, arch, mm, lo):
    T = u.shape[0]
    H, R = arch["num_attention_heads"], arch["kv_lora_rank"]
    nope, rope = arch["qk_nope_head_dim"], arch["qk_rope_head_dim"]
    at = jnp.arange(T, dtype=jnp.float32)
    q = mm(u, p["wq"]).reshape(T, H, nope + rope)
    q = jnp.concatenate([q[..., :nope], _rotate(q[..., nope:], at, arch)],
                        -1)
    ckv = mm(u, p["wkv_a"])
    c = _rmsnorm(ckv[:, :R], p["kv_norm"], arch["rms_norm_eps"])
    k_pe = _rotate(ckv[:, R:], at, arch)
    k = jnp.concatenate([
        jnp.einsum("tr,rhd->thd", lo(c), lo(p["wk_b"])),
        jnp.broadcast_to(k_pe[:, None], (T, H, rope))], -1)
    v = jnp.einsum("tr,rhd->thd", lo(c), lo(p["wv_b"]))

    def rows(qb, start):
        s = jnp.einsum("qhd,khd->hqk", lo(qb), lo(k)) \
            / np.sqrt(nope + rope)
        seen = (start + jnp.arange(qb.shape[0]))[:, None] \
            >= jnp.arange(T)[None]
        s = jnp.where(seen, s, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", lo(jax.nn.softmax(s, axis=-1)),
                          lo(v))

    return mm(_in_blocks(rows, q).reshape(T, -1), p["wo"])


def _swiglu(h, gate, up, down, mm):
    return mm(jax.nn.silu(mm(h, gate)) * mm(h, up), down)


def choose(h, p, arch):
    """-> (the chosen experts (T, k), their weights (T, k)): the group
    limit, then the k largest biased scores, weighted by the unbiased."""
    T, E = h.shape[0], p["router"].shape[1]
    n_group = 1 if "group_limit" in arch.get("leave_out", ()) \
        else arch["n_group"]
    scores = jax.nn.sigmoid(h @ p["router"])
    biased = scores + p["router_bias"]
    if n_group > 1:
        groups = biased.reshape(T, n_group, E // n_group)
        best_two = jnp.sort(groups, axis=-1)[..., -2:].sum(-1)
        _, kept = jax.lax.top_k(best_two, arch["topk_group"])
        stays = jnp.any(kept[:, :, None] == jnp.arange(n_group), axis=1)
        biased = jnp.where(jnp.repeat(stays, E // n_group, axis=1), biased,
                           -jnp.inf)
    _, chosen = jax.lax.top_k(biased, arch["num_experts_per_tok"])
    w = jnp.take_along_axis(scores, chosen, axis=1)
    if arch["norm_topk_prob"]:
        w = w / jnp.sum(w, axis=1, keepdims=True)
    return chosen, w * arch["routed_scaling_factor"]


def routed_shared(h, p, arch, mm, lo, held=None):
    """-> (the held experts' part plus the shared expert, the experts each
    token chose (T, k)). `held` = (offset, count, shared counted or not):
    by default what the weights hold, from the configuration's offset."""
    T, E = h.shape[0], p["router"].shape[1]
    offset, count, with_shared = held or (
        arch["expert_offset"], p["we_up"].shape[0], True)
    chosen, w = choose(h, p, arch)
    weights = jnp.zeros((T, E)).at[jnp.arange(T)[:, None], chosen].set(w)
    weights = weights[:, offset:offset + count]  # zero if unchosen

    def rows(block, at):  # a row and its weights side by side
        r, mine = block[:, :h.shape[1]], block[:, h.shape[1]:]
        a = jax.nn.silu(jnp.einsum("td,edf->etf", lo(r), lo(p["we_gate"]))) \
            * jnp.einsum("td,edf->etf", lo(r), lo(p["we_up"]))
        out = jnp.einsum("etf,efd->etd", lo(a), lo(p["we_down"]))
        y = jnp.einsum("te,etd->td", mine, out)
        if with_shared:
            y = y + _swiglu(r, p["ws_gate"], p["ws_up"], p["ws_down"], mm)
        return y

    return _in_blocks(rows, jnp.concatenate([h, weights], axis=1)), chosen


def _tools(operand_dtype):
    def lo(a):
        return a.astype(operand_dtype).astype(jnp.float32)

    return (lambda a, w: lo(a) @ lo(w)), lo


def mixer(u, p, kind: str, arch: dict, operand_dtype=jnp.float32,
          state_dtype=jnp.float32, last=None):
    """One layer's mixer on its normed rows u (T, hidden), `p` that
    layer's weights in float32. Returns (the mixer's output; S or None;
    the conv window or None; (g, beta) or None). `operand_dtype` below
    float32 rounds every matrix product's operands to it first, and
    `state_dtype` rounds S after every token: the same mathematics
    "computed in a lower precision", for the readings that set a
    tolerance; the reference itself never uses either."""
    mm, lo = _tools(operand_dtype)
    if kind == "kda":
        return kda_mixer(u, p, arch, mm, state_dtype, last)
    if kind == "mla":
        return mla_mixer(u, p, arch, mm, lo), None, None, None
    raise ValueError(f"unknown layer kind {kind!r}")


def feed_forward(h, p, routed: bool, arch: dict, operand_dtype=jnp.float32,
                 held=None):
    """One layer's second half on its normed rows h (T, hidden): (its
    output; the experts chosen (T, k), or None for a dense layer)."""
    mm, lo = _tools(operand_dtype)
    if not routed:
        return _swiglu(h, p["w_gate"], p["w_up"], p["w_down"], mm), None
    return routed_shared(h, p, arch, mm, lo, held)


def layer(x, p, kind: str, routed: bool, arch: dict,
          operand_dtype=jnp.float32, state_dtype=jnp.float32):
    """One layer on x (T, hidden), `p` that layer's weights. Returns (x,
    the experts each token chose (T, k) or None)."""
    p = jax.tree.map(lambda a: a.astype(jnp.float32), p)
    eps = arch["rms_norm_eps"]
    x = x + mixer(_rmsnorm(x, p["mixer_norm"], eps), p, kind, arch,
                  operand_dtype, state_dtype)[0]
    y, chosen = feed_forward(_rmsnorm(x, p["ffn_norm"], eps), p, routed,
                             arch, operand_dtype)
    return x + y, chosen


@functools.partial(jax.jit, static_argnames=("kind", "routed", "arch",
                                             "operand_dtype", "state_dtype"))
def _layer(x, p, kind, routed, arch: tuple, operand_dtype, state_dtype):
    # one program a KIND of layer (three of them), not one a layer
    return layer(x, p, kind, routed, dict(arch), operand_dtype, state_dtype)


def layers_of(arch: dict) -> list[tuple[str, bool]]:
    """(the mixer's kind, routed or dense) of every layer."""
    return [(kind, i >= arch["first_k_dense_replace"])
            for i, kind in enumerate(kinds_of(arch))]


def embed(params, tokens):
    return params["wte"][tokens].astype(jnp.float32)


def head(params, x, arch: dict):
    x = _rmsnorm(x, params["lnf"].astype(jnp.float32), arch["rms_norm_eps"])
    return x @ params["lm_head"].astype(jnp.float32)


def forward(params, tokens, arch: dict, operand_dtype=jnp.float32,
            state_dtype=jnp.float32):
    """tokens (T,) int32 -> (logits (T, padded vocab) float32, the experts
    chosen (expert layers, T, k)). One layer's weights in float32 at a
    time."""
    frozen = freeze(arch)
    with jax.default_matmul_precision("highest"):
        x = embed(params, tokens)
        chosen = []
        for (kind, routed), p in zip(layers_of(arch), params["layers"],
                                     strict=True):
            x, c = _layer(x, p, kind, routed, frozen, operand_dtype,
                          state_dtype)
            if routed:
                chosen.append(c)
        logits = head(params, x, arch)
    return logits, jnp.stack(chosen)


def log_softmax(logits, vocab_size: int):
    """Over the real vocabulary: padded rows of the head are masked out."""
    mask = jnp.arange(logits.shape[-1]) < vocab_size
    return jax.nn.log_softmax(jnp.where(mask, logits, -jnp.inf), axis=-1)


def serve_reference(params, model: dict, cases: list[dict],
                    pad_to: int = 320, arch: dict | None = None,
                    operand_dtype=jnp.float32,
                    state_dtype=jnp.float32) -> list[list[float]]:
    """For the serve cells: log p(tokens[i] | prompt + tokens[:i]) of each
    case's streamed tokens, by one full forward pass over the whole
    sequence (teacher forcing; no cache, no state carried in), with the
    very weights the engine serves. `model` carries the harness's five
    sizes; what this family needs beyond them it reads from its
    configuration file. Sequences are padded at the end to a multiple of
    `pad_to`, which a causal model's earlier positions cannot see, so few
    programs serve all (320: the harness's four check requests, 32 to 308
    tokens, are ONE shape, three programs, where a multiple of 64 made
    twelve and 100 s of a run's tail on the chip's host: PR 61). `arch`
    and the two dtypes are for the controls (another share, a lower
    precision, a mechanism left out), which must NOT pass the check."""
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
