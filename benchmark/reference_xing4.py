"""The xing4 stack, plainly: Xing4.0-29B-A4B's forward pass in float32
`jax.numpy`, written from the model's description (XingChen-AGI/
Xing4.0-29B-A4B, `model_type` xing4_0: DeepSeek-V3's attention, rotation
and feed-forwards on the residual path of Manifold-Constrained
Hyper-Connections, arXiv:2512.24880) and not from
`ray_tpu/models/xing4.py`.

No kernels, no cache, no absorbed form, no tiles, no dispatch: a Python
loop over the layers; a token's residual state as an (n, C) matrix; every
row's key and value heads up-projected from its latent; one full score
matrix a head under a causal mask; in an expert layer EVERY held expert
computed for EVERY token and combined with the routing weights, which are
zero for the experts a token did not choose. Only the rows go in blocks of
`ROW_BLOCK` (attention's queries, the experts' rows: the score matrices
of 32 heads and the experts' hidden rows at 32k rows would not fit
otherwise), which changes no number. One layer's weights are cast up to
float32 at a time.

With X (n, C) a token's state, n = `hc_mult` = 4:

  entry: X[i] = wte[token] for every i; exit: x = sum_i X[i]
  a half-layer (its maps phi (nC, n^2 + 2n), alpha (3), b_pre (n), b_post
  (n), b_res (n, n)):
    x = vec(X) (row-major: stream 0's lanes first)
    u = (x * rsqrt(mean(x^2) + rms_norm_eps)) @ phi
    H_pre = sigmoid(alpha[0] u[0:n] + b_pre)
    H_post = 2 sigmoid(alpha[1] u[n:2n] + b_post)
    M = exp(clip(alpha[2] mat(u[2n:]) + b_res, clamp_min, clamp_max))
    hc_sinkhorn_iters times: M = M / (colsum(M) + hc_eps);
                             M = M / (rowsum(M) + hc_eps);   H_res = M
    h = sum_i H_pre[i] X[i];  y = F(rmsnorm(h) * norm scale)
    X'[i] = sum_j H_res[i, j] X[j] + H_post[i] y
  attention (F of the first half):
    c_q = rmsnorm(h Wq_a); q = c_q Wq_b -> 32 heads x (nope 128 | pe 64)
    [c_kv | k_pe] = h Wkv_a (512 | 64); c_kv = rmsnorm(c_kv)
    [k_nope | v] = c_kv Wkv_b -> 32 heads x (128 | 128)
    q_pe and k_pe (one for all heads) rotated: interleaved pairs (2i,
      2i + 1), angle t * f_i, f YaRN's blend of theta^(-2i/64) and that
      over `factor` (pairs turning more than beta_fast times over the
      original 4,096 positions keep theirs, fewer than beta_slow are
      divided, a linear ramp over whole pair indices between)
    z[t,s] = (q_nope_t . k_nope_s + q_pe_t . k_pe_s) * mscale^2 / sqrt(192),
      mscale = 0.1 mscale_all_dim ln(factor) + 1;  s <= t
    out = softmax_s(z) v -> (32 x 128) Wo
  feed-forward (F of the second half): layers below
    `first_k_dense_replace` a dense SwiGLU of `intermediate_size`; the
    others s = sigmoid_float32(h Wrouter) over ALL experts; the 4 largest
    of s + e_score_correction_bias chosen; weights s at the chosen,
    divided by their sum, times `routed_scaling_factor`;
    out = sum over the HELD experts e of weight[e] * swiglu_e(h)
          + swiglu_shared(h)
  logits = rmsnorm(sum_i X[i]) Whead                  (head not tied)

The share: this chip holds the experts its weights stack, from
`expert_offset` on, and the shared expert whole. What the absent experts
would have added is left out, here as in the program; the vocabulary is
the held slice.

Assumed (each also listed in the configuration file): entry and exit of
the streams as above (Hyper-Connections, arXiv:2409.19606, section 3);
columns before rows in a Sinkhorn iteration and the epsilon added to the
sums; no scale on the RMS of vec(X); interleaved pairs and
`mscale^2` as DeepSeek-V3's `transformers` implementation has them; the
MTP layer is not part of this model. The weights are the program's own
pytree (one dict a layer; `wk_b` and `wv_b` are Wkv_b's two column
groups; `hc_attn` and `hc_ffn` the two halves' maps). Ties among router
scores break as `lax.top_k` does (the lower index).

On a TPU a float32 matmul runs in lower precision unless asked otherwise,
so every entry point runs under `jax.default_matmul_precision("highest")`.
The keys of `arch` beyond the published ones (`h_post_factor`,
`hc_dynamic`, `mscale_squared`, `score_scale`, `rope_scaling` None,
`coef_dtype`) exist for the controls, which compute this side wrongly on purpose.
"""

from __future__ import annotations

import functools
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np

_CONFIG = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "configs", "xing4.0-29b-a4b.json")
ARCH_KEYS = ("num_hidden_layers", "first_k_dense_replace",
             "num_attention_heads", "q_lora_rank", "kv_lora_rank",
             "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
             "rope_theta", "hc_mult", "hc_sinkhorn_iters", "hc_eps",
             "mhc_h_res_clamp_min", "mhc_h_res_clamp_max",
             "num_experts_per_tok", "norm_topk_prob",
             "routed_scaling_factor", "n_shared_experts", "expert_offset",
             "rms_norm_eps", "vocab_size")
ROW_BLOCK = 256  # rows a block; sequences are padded to whole blocks


def published_arch() -> dict:
    """The keys of the published config this reference needs, from the
    benchmark's configuration file."""
    with open(_CONFIG) as f:
        config = json.load(f)
    arch = {k: config[k] for k in ARCH_KEYS}
    arch["rope_scaling"] = tuple(sorted(config["rope_scaling"].items()))
    return arch


def _rmsnorm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * weight


def frequencies(arch: dict) -> np.ndarray:
    """The angle a position of each rotated pair: theta^(-2i/width), and
    under `rope_scaling` (type yarn) YaRN's blend of it."""
    width, theta = arch["qk_rope_head_dim"], arch["rope_theta"]
    plain = theta ** (-2.0 * np.arange(width // 2) / width)
    scaling = dict(arch.get("rope_scaling") or ())
    if not scaling:
        return plain
    original = scaling["original_max_position_embeddings"]

    def pair(turns):  # the pair that turns `turns` times over `original`
        return width * math.log(original / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(pair(scaling["beta_fast"])), 0)
    high = min(math.ceil(pair(scaling["beta_slow"])), width - 1)
    ramp = np.clip((np.arange(width // 2) - low) / max(high - low, 0.001),
                   0.0, 1.0)
    return plain * (1.0 - ramp) + plain / scaling["factor"] * ramp


def score_scale(arch: dict) -> float:
    if arch.get("score_scale") is not None:  # a control's: given outright
        return arch["score_scale"]
    scale = 1.0 / math.sqrt(arch["qk_nope_head_dim"]
                            + arch["qk_rope_head_dim"])
    scaling = dict(arch.get("rope_scaling") or ())
    if scaling and arch.get("mscale_squared", True):
        scale *= (0.1 * scaling["mscale_all_dim"]
                  * math.log(scaling["factor"]) + 1.0) ** 2
    return scale


def _rotate(x, arch: dict):
    """x (T, [heads,] width) rotated by the row's position, interleaved
    pairs (2i, 2i + 1)."""
    T, width = x.shape[0], x.shape[-1]
    angle = jnp.arange(T, dtype=jnp.float32)[:, None] \
        * jnp.asarray(frequencies(arch), jnp.float32)
    if x.ndim == 3:
        angle = angle[:, None, :]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     axis=-1).reshape(*x.shape[:-1], width)


def _blocks(fn, *rows):
    """``fn`` on blocks of `ROW_BLOCK` of the leading dimension (T, a
    multiple of the block or below it)."""
    T = rows[0].shape[0]
    nb = max(1, T // ROW_BLOCK)
    out = jax.lax.map(fn, tuple(a.reshape(nb, T // nb, *a.shape[1:])
                                for a in rows))
    return jax.tree.map(lambda a: a.reshape(T, *a.shape[2:]), out)


def attention(h, p, arch: dict, mm, lo):
    """One attention on its normed rows h (T, hidden), T a multiple of
    `ROW_BLOCK` or below it -> out (T, hidden)."""
    T = h.shape[0]
    H, R = arch["num_attention_heads"], arch["kv_lora_rank"]
    dn, dr, dv = (arch["qk_nope_head_dim"], arch["qk_rope_head_dim"],
                  arch["v_head_dim"])
    eps = arch["rms_norm_eps"]
    c_q = _rmsnorm(mm(h, p["wq_a"]), p["q_norm"], eps)
    q = mm(c_q, p["wq_b"]).reshape(T, H, dn + dr)
    q = jnp.concatenate([q[..., :dn], _rotate(q[..., dn:], arch)], axis=-1)
    ckv = mm(h, p["wkv_a"])
    c_kv = _rmsnorm(ckv[:, :R], p["kv_norm"], eps)
    k_pe = _rotate(ckv[:, R:], arch)
    k_nope = jnp.einsum("sr,rhd->shd", lo(c_kv), lo(p["wk_b"]))
    v = jnp.einsum("sr,rhd->shd", lo(c_kv), lo(p["wv_b"]))
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_pe[:, None, :], (T, H, dr))], axis=-1)
    at, scale = jnp.arange(T), score_scale(arch)

    def rows(args):
        q_b, t_b = args
        z = jnp.einsum("thd,shd->hts", lo(q_b), lo(k)) * scale
        seen = at[None, :] <= t_b[:, None]
        prob = jax.nn.softmax(jnp.where(seen[None], z, -jnp.inf), axis=-1)
        return jnp.einsum("hts,shd->thd", lo(prob), lo(v))

    return mm(_blocks(rows, q, at).reshape(T, H * dv), p["wo"])


def _swiglu(h, gate, up, down, mm):
    return mm(jax.nn.silu(mm(h, gate)) * mm(h, up), down)


def dense(h, p, mm):
    return _swiglu(h, p["w_gate"], p["w_up"], p["w_down"], mm)


def experts(h, p, arch: dict, mm, lo):
    """-> (the held experts' part of the routed sum plus the shared
    expert, the experts each token chose (T, k))."""
    E = p["router"].shape[1]  # the router's width, whatever is held
    offset, count = arch["expert_offset"], p["we_up"].shape[0]

    def rows(args):
        (hb,) = args
        n = hb.shape[0]
        scores = jax.nn.sigmoid(hb @ p["router"])  # float32, all experts
        _, chosen = jax.lax.top_k(scores + p["router_bias"],
                                  arch["num_experts_per_tok"])
        top = jnp.take_along_axis(scores, chosen, axis=-1)
        if arch["norm_topk_prob"]:
            top = top / jnp.sum(top, axis=-1, keepdims=True)
        top = top * (arch["routed_scaling_factor"] or 1.0)
        weights = jnp.zeros((n, E)).at[jnp.arange(n)[:, None],
                                       chosen].set(top)
        weights = weights[:, offset:offset + count]  # zero if unchosen
        gate = jnp.einsum("td,edf->etf", lo(hb), lo(p["we_gate"]))
        up = jnp.einsum("td,edf->etf", lo(hb), lo(p["we_up"]))
        out = jnp.einsum("etf,efd->etd", lo(jax.nn.silu(gate) * up),
                         lo(p["we_down"]))
        y = jnp.einsum("te,etd->td", weights, out)
        if arch["n_shared_experts"]:
            y = y + _swiglu(hb, p["ws_gate"], p["ws_up"], p["ws_down"], mm)
        return y, chosen

    return _blocks(rows, h)


def _lower(operand_dtype):
    def lo(a):
        return a.astype(operand_dtype).astype(jnp.float32)

    return lo, (lambda a, w: lo(a) @ lo(w))


def sinkhorn(M, iters: int, eps: float):
    """M (..., n, n) positive: `iters` times columns, then rows, divided by
    their sums plus eps."""
    for _ in range(iters):
        M = M / (jnp.sum(M, axis=-2, keepdims=True) + eps)
        M = M / (jnp.sum(M, axis=-1, keepdims=True) + eps)
    return M


def mhc_maps(X, m, arch: dict):
    """The states X (T, n, C) through a half-layer's maps `m` -> (H_pre
    (T, n), H_post (T, n), H_res (T, n, n)). `coef_dtype` (a control)
    rounds the normed state, phi and the logits to it."""
    n, T = arch["hc_mult"], X.shape[0]
    cd = arch.get("coef_dtype", "float32")
    low = lambda a: a.astype(cd).astype(jnp.float32)
    x = X.reshape(T, -1)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                          + arch["rms_norm_eps"])
    u = low(low(x) @ low(m["phi"]))
    alpha = m["alpha"] if arch.get("hc_dynamic", True) \
        else jnp.zeros_like(m["alpha"])
    pre = jax.nn.sigmoid(low(alpha[0] * u[:, :n] + m["b_pre"]))
    post = arch.get("h_post_factor", 2.0) * jax.nn.sigmoid(
        low(alpha[1] * u[:, n:2 * n] + m["b_post"]))
    logits = low(alpha[2] * u[:, 2 * n:].reshape(T, n, n) + m["b_res"])
    M = jnp.exp(jnp.clip(logits, arch["mhc_h_res_clamp_min"],
                         arch["mhc_h_res_clamp_max"]))
    return pre, post, low(sinkhorn(M, arch["hc_sinkhorn_iters"],
                                   arch["hc_eps"]))


def mix_pre(X, pre):
    return jnp.einsum("ti,tic->tc", pre, X)


def mix_post(X, y, post, res):
    return jnp.einsum("tij,tjc->tic", res, X) + post[:, :, None] \
        * y[:, None, :]


def attention_half(h, p, arch: dict, operand_dtype=jnp.float32):
    """What a block's attention returns for its normed rows h (T, hidden)
    and that layer's weights in float32. `operand_dtype` below float32
    rounds every matrix product's operands to it first: the same
    mathematics "computed in a lower precision", for the readings that
    set a tolerance; the reference itself never uses it."""
    lo, mm = _lower(operand_dtype)
    return attention(h, p, arch, mm, lo)


def ffn_half(h, p, routed: bool, arch: dict, operand_dtype=jnp.float32):
    """What a block's feed-forward returns -> (y, the experts each token
    chose (T, k) or None)."""
    lo, mm = _lower(operand_dtype)
    if routed:
        return experts(h, p, arch, mm, lo)
    return dense(h, p, mm), None


def half_layer(X, p, half: str, routed: bool, arch: dict,
               operand_dtype=jnp.float32):
    """One half-layer ("attn" or "ffn") on the states X (T, n, C) -> (X',
    the experts chosen or None)."""
    pre, post, res = mhc_maps(X, p["hc_" + half], arch)
    h = _rmsnorm(mix_pre(X, pre), p[half + "_norm"], arch["rms_norm_eps"])
    if half == "attn":
        y, chosen = attention_half(h, p, arch, operand_dtype), None
    else:
        y, chosen = ffn_half(h, p, routed, arch, operand_dtype)
    return mix_post(X, y, post, res), chosen


def layer(X, p, routed: bool, arch: dict, operand_dtype=jnp.float32):
    """One block on X (T, n, C), `p` that layer's weights. Returns (X, the
    experts each token chose (T, k), or None)."""
    p = jax.tree.map(lambda a: a.astype(jnp.float32), p)
    X, _ = half_layer(X, p, "attn", routed, arch, operand_dtype)
    return half_layer(X, p, "ffn", routed, arch, operand_dtype)


@functools.partial(jax.jit, static_argnames=("routed", "arch",
                                             "operand_dtype"),
                   donate_argnums=(0,))
def _layer(X, p, routed, arch: tuple, operand_dtype):
    return layer(X, p, routed, dict(arch), operand_dtype)


def layers_of(arch: dict) -> list[bool]:
    """Whether each layer held has routed experts."""
    return [i >= arch["first_k_dense_replace"]
            for i in range(arch["num_hidden_layers"])]


def frozen(arch: dict) -> tuple:
    return tuple(sorted(arch.items()))


def enter(params, tokens, arch: dict):
    """tokens (T,) -> the states X (T, n, C) float32 the stack starts
    from."""
    x = params["wte"][jnp.asarray(tokens, jnp.int32)].astype(jnp.float32)
    return jnp.repeat(x[:, None, :], arch["hc_mult"], axis=1)


def forward(params, tokens, arch: dict, operand_dtype=jnp.float32):
    """tokens (T,) int32, T a multiple of `ROW_BLOCK` or below it ->
    (logits (T, padded vocab) float32, the experts chosen (expert layers,
    T, k)). One layer's weights in float32 at a time."""
    with jax.default_matmul_precision("highest"):
        X = enter(params, tokens, arch)
        chosen = []
        for routed, p in zip(layers_of(arch), params["layers"], strict=True):
            X, c = _layer(X, p, routed, frozen(arch), operand_dtype)
            if c is not None:
                chosen.append(c)
        x = _rmsnorm(jnp.sum(X, axis=1), params["lnf"].astype(jnp.float32),
                     arch["rms_norm_eps"])
        logits = x @ params["lm_head"].astype(jnp.float32)
    return logits, jnp.stack(chosen)


def log_softmax(logits, vocab_size: int):
    """Over the real vocabulary: padded rows of the head are masked out."""
    mask = jnp.arange(logits.shape[-1]) < vocab_size
    return jax.nn.log_softmax(jnp.where(mask, logits, -jnp.inf), axis=-1)


def serve_reference(params, model: dict, cases: list[dict],
                    arch: dict | None = None,
                    operand_dtype=jnp.float32) -> list[list[float]]:
    """For the serve cells: log p(tokens[i] | prompt + tokens[:i]) of each
    case's streamed tokens, by one full forward pass over the whole
    sequence (teacher forcing; no cache), with the very weights the engine
    serves. `model` carries the harness's five sizes; what this family
    needs beyond them it reads from its configuration file. Sequences are
    padded at the end to whole blocks of `ROW_BLOCK` rows, which a causal
    model's earlier positions cannot see. `arch` and the dtype are for the
    controls (another share, a lower precision, a mechanism left out),
    which must NOT pass the check."""
    arch = arch or published_arch()
    out = []
    for c in cases:
        seq = list(c["prompt"]) + list(c["tokens"])
        width = -(-len(seq) // ROW_BLOCK) * ROW_BLOCK
        row = np.zeros((width,), np.int32)
        row[:len(seq)] = seq
        logits, _ = forward(params, jnp.asarray(row), arch, operand_dtype)
        n = len(c["prompt"])
        logp = np.asarray(log_softmax(
            logits[n - 1:n - 1 + len(c["tokens"])], arch["vocab_size"]))
        out.append([float(logp[j, t]) for j, t in enumerate(c["tokens"])])
    return out
