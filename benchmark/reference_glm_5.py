"""The glm_dsa stack, plainly: GLM-5's forward pass in float32
`jax.numpy`, written from the model's description (zai-org/GLM-5,
`model_type` glm_moe_dsa, whose keys are DeepSeek-V3.2's) and not from
`ray_tpu/models/glm_dsa.py`.

No kernels, no cache, no absorbed form, no tiles, no dispatch: a Python
loop over the layers; every row's key and value heads up-projected from
its latent; the indexer's choice as a mask over a full score matrix; in
an expert layer EVERY held expert computed for EVERY token and combined
with the routing weights, which are zero for the experts a token did not
choose. Only the query rows go in blocks of `ROW_BLOCK` (the score
matrices of 64 heads at 12,288 rows would not fit otherwise), which
changes no number. One layer's weights are cast up to float32 at a time.

Every block is ``x = x + attn(rmsnorm(x)); x = x + ffn(rmsnorm(x))`` (eps
`rms_norm_eps`), every block's attention the same:

  h = rmsnorm(x)
  c_q = rmsnorm(h Wq_a)                              (q_lora_rank)
  q = c_q Wq_b -> 64 heads x (nope 192 | pe 64); q_pe rotated
  [c_kv | k_pe] = h Wkv_a (512 | 64); c_kv = rmsnorm(c_kv); k_pe rotated,
      one for all heads
  [k_nope | v] = c_kv Wkv_b -> 64 heads x (192 | 256)
  z[t,s] = (q_nope_t . k_nope_s + q_pe_t . k_pe_s) / sqrt(256)
  rotation: theta `rope_theta`, interleaved pairs (2i, 2i + 1), angle
      t * theta^(-2i/64)
  indexer: qI = c_q WI_qb -> 32 heads x 128; kI = layernorm(h WI_k) (128,
      with bias, one for all heads); the first 64 lanes of both rotated
      the same way; w = h WI_w (32)
      I[t,s] = sum_j w[t,j] relu(qI[t,j] . kI[s]) / sqrt(32 * 128), s <= t
      row t attends to the min(2048, t + 1) slots of largest I[t, .]
      (of equal scores the earliest) and to no other
  out = softmax over the chosen s of z[t,s], times v, -> (64 x 256) Wo
  feed-forward: layers below `first_k_dense_replace` a dense SwiGLU of
      `intermediate_size`; the others
      s = sigmoid_float32(h Wrouter) over ALL experts; the 8 largest of
      s + e_score_correction_bias chosen; weights s at the chosen, divided
      by their sum, times `routed_scaling_factor`;
      out = sum over the HELD experts e of weight[e] * swiglu_e(h)
            + swiglu_shared(h)
  logits = rmsnorm(x) Whead                      (head not tied)

The share: this chip holds the experts its weights stack, from
`expert_offset` on, and the shared expert whole. What the absent experts
would have added is left out, here as in the program; the vocabulary is
the held slice.

Assumed (each also listed in the configuration file): the indexer is
DeepSeek-V3.2-Exp's published one (LayerNorm with bias on kI, rotated
lanes first, qI from the normed query latent, w from the normed hidden
rows), without its Hadamard rotation and FP8 rows (orthogonal: equal
scores in exact arithmetic); `head_dim` 64 in the config is the rotated
width; `rope_type` default is no scaling; the MTP layer is not part of
this model. The weights are the program's own pytree (one dict a layer;
`wk_b` and `wv_b` are Wkv_b's two column groups). Ties among router
scores break as `lax.top_k` does (the lower index).

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
                       "configs", "glm-5.json")
ARCH_KEYS = ("num_hidden_layers", "first_k_dense_replace",
             "num_attention_heads", "q_lora_rank", "kv_lora_rank",
             "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
             "index_n_heads", "index_head_dim", "index_topk",
             "rope_interleave", "indexer_rope_interleave",
             "num_experts_per_tok", "norm_topk_prob",
             "routed_scaling_factor", "n_shared_experts", "expert_offset",
             "rms_norm_eps", "vocab_size")
ROW_BLOCK = 256  # query rows a block; sequences are padded to whole blocks


def published_arch() -> dict:
    """The keys of the published config this reference needs, from the
    benchmark's configuration file."""
    with open(_CONFIG) as f:
        config = json.load(f)
    arch = {k: config[k] for k in ARCH_KEYS}
    arch["rope_theta"] = config["rope_parameters"]["rope_theta"]
    return arch


def _rmsnorm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * weight


def _layernorm(x, weight, bias, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * weight + bias


def _rotate(x, theta: float, width: int, how="interleaved"):
    """x (T, [heads,] D): the first `width` lanes rotated by the row's
    position. `how` "interleaved": pairs (2i, 2i + 1), as published;
    "half": pairs (i, i + width / 2) and "none", for the controls."""
    if how == "none":
        return x
    T = x.shape[0]
    half = width // 2
    angle = jnp.arange(T, dtype=jnp.float32)[:, None] \
        * theta ** (-2.0 * jnp.arange(half, dtype=jnp.float32) / width)
    if x.ndim == 3:
        angle = angle[:, None, :]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    if how == "half":
        a, b = x[..., :half], x[..., half:width]
        turned = jnp.concatenate([a * cos - b * sin, a * sin + b * cos], -1)
    else:
        a, b = x[..., 0:width:2], x[..., 1:width:2]
        turned = jnp.stack([a * cos - b * sin, a * sin + b * cos],
                           axis=-1).reshape(*x.shape[:-1], width)
    return jnp.concatenate([turned, x[..., width:]], axis=-1)


def _how(arch, key):
    how = arch[key]
    return {True: "interleaved", False: "half"}.get(how, how)


def choice(scores, seen, k):
    """Row t's slots: the k of largest `scores` (rows, S) among `seen`,
    all of them while there are no more, of equal scores the earliest.
    `k` None: every slot seen (the selection left out: a control)."""
    if k is None or scores.shape[-1] <= k:
        return seen
    scores = jnp.where(seen, scores, -jnp.inf)
    kth = jax.lax.top_k(scores, k)[0][:, -1:]
    above = scores > kth
    tied = scores == kth
    spare = k - jnp.sum(above, axis=-1, keepdims=True)
    return seen & (above | (tied & (jnp.cumsum(tied, axis=-1) <= spare)))


def attention(h, p, arch: dict, mm, lo, chosen=None):
    """One attention half on its normed rows h (T, hidden), T a multiple
    of `ROW_BLOCK` or below it -> (out (T, hidden), the slots every row
    chose (T, T) bool). `chosen` given: attended as given, the indexer's
    own choice still returned."""
    T = h.shape[0]
    H, R = arch["num_attention_heads"], arch["kv_lora_rank"]
    dn, dr, dv = (arch["qk_nope_head_dim"], arch["qk_rope_head_dim"],
                  arch["v_head_dim"])
    HI, Di = arch["index_n_heads"], arch["index_head_dim"]
    eps, theta = arch["rms_norm_eps"], arch["rope_theta"]
    c_q = _rmsnorm(mm(h, p["wq_a"]), p["q_norm"], eps)
    q = mm(c_q, p["wq_b"]).reshape(T, H, dn + dr)
    q = jnp.concatenate(
        [q[..., :dn], _rotate(q[..., dn:], theta, dr,
                              _how(arch, "rope_interleave"))], axis=-1)
    ckv = mm(h, p["wkv_a"])
    c_kv = _rmsnorm(ckv[:, :R], p["kv_norm"], eps)
    k_pe = ckv[:, R:]
    if arch.get("k_pe_rotated", True):
        k_pe = _rotate(k_pe, theta, dr, _how(arch, "rope_interleave"))
    k_nope = jnp.einsum("sr,rhd->shd", lo(c_kv), lo(p["wk_b"]))
    v = jnp.einsum("sr,rhd->shd", lo(c_kv), lo(p["wv_b"]))
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_pe[:, None, :], (T, H, dr))], axis=-1)
    # the indexer
    ihow = _how(arch, "indexer_rope_interleave")
    qi = _rotate(mm(c_q, p["wiq_b"]).reshape(T, HI, Di), theta, dr, ihow)
    ki = _rotate(_layernorm(mm(h, p["wik"]), p["ik_norm"], p["ik_bias"],
                            eps), theta, dr, ihow)
    w = mm(h, p["wiw"]) / np.sqrt(HI * Di)
    act = jax.nn.relu if arch.get("index_relu", True) else (lambda a: a)
    at = jnp.arange(T)

    def rows(args):
        q_b, qi_b, w_b, t_b, given = args
        seen = at[None, :] <= t_b[:, None]
        index = jnp.einsum("thd,sd->ths", lo(qi_b), lo(ki))
        index = jnp.einsum("ths,th->ts", act(index), w_b)
        own = choice(index, seen, arch["index_topk"])
        use = own if chosen is None else given
        z = jnp.einsum("thd,shd->hts", lo(q_b), lo(k)) \
            / np.sqrt(arch.get("score_width", dn + dr))
        prob = jax.nn.softmax(jnp.where(use[None], z, -jnp.inf), axis=-1)
        return jnp.einsum("hts,shd->thd", lo(prob), lo(v)), own

    nb = max(1, T // ROW_BLOCK)
    split = lambda a: a.reshape(nb, T // nb, *a.shape[1:])
    given = split(chosen if chosen is not None else jnp.zeros((T, 1), bool))
    a, own = jax.lax.map(rows, (split(q), split(qi), split(w), split(at),
                                given))
    return mm(a.reshape(T, H * dv), p["wo"]), own.reshape(T, T)


def _swiglu(h, gate, up, down, mm):
    return mm(jax.nn.silu(mm(h, gate)) * mm(h, up), down)


def dense(h, p, mm):
    return _swiglu(h, p["w_gate"], p["w_up"], p["w_down"], mm)


def experts(h, p, arch: dict, mm, lo):
    """-> (the held experts' part of the routed sum plus the shared
    expert, the experts each token chose (T, k))."""
    T = h.shape[0]
    E = p["router"].shape[1]  # the router's width, whatever is held
    offset, count = arch["expert_offset"], p["we_up"].shape[0]
    scores = jax.nn.sigmoid(h @ p["router"])  # float32, all experts
    _, chosen = jax.lax.top_k(scores + p["router_bias"],
                              arch["num_experts_per_tok"])
    top = jnp.take_along_axis(scores, chosen, axis=-1)
    if arch["norm_topk_prob"]:
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    top = top * (arch["routed_scaling_factor"] or 1.0)
    weights = jnp.zeros((T, E)).at[jnp.arange(T)[:, None], chosen].set(top)
    weights = weights[:, offset:offset + count]  # zero if unchosen
    gate = jnp.einsum("td,edf->etf", lo(h), lo(p["we_gate"]))
    up = jnp.einsum("td,edf->etf", lo(h), lo(p["we_up"]))
    out = jnp.einsum("etf,efd->etd", lo(jax.nn.silu(gate) * up),
                     lo(p["we_down"]))
    y = jnp.einsum("te,etd->td", weights, out)
    if arch["n_shared_experts"]:
        y = y + _swiglu(h, p["ws_gate"], p["ws_up"], p["ws_down"], mm)
    return y, chosen


def _lower(operand_dtype):
    def lo(a):
        return a.astype(operand_dtype).astype(jnp.float32)

    return lo, (lambda a, w: lo(a) @ lo(w))


def attention_half(h, p, arch: dict, operand_dtype=jnp.float32,
                   chosen=None):
    """What a block's attention adds to x, from its normed rows h (T,
    hidden) and that layer's weights in float32, and the slots its rows
    chose. `operand_dtype` below float32 rounds every matrix product's
    operands to it first: the same mathematics "computed in a lower
    precision", for the readings that set a tolerance; the reference
    itself never uses it."""
    lo, mm = _lower(operand_dtype)
    return attention(h, p, arch, mm, lo, chosen)


def ffn_half(h, p, routed: bool, arch: dict, operand_dtype=jnp.float32):
    """What a block's feed-forward adds to x -> (y, the experts each token
    chose (T, k) or None)."""
    lo, mm = _lower(operand_dtype)
    if routed:
        return experts(h, p, arch, mm, lo)
    return dense(h, p, mm), None


def layer(x, p, routed: bool, arch: dict, operand_dtype=jnp.float32):
    """One block on x (T, hidden), `p` that layer's weights. Returns (x,
    the experts each token chose (T, k), or None)."""
    p = jax.tree.map(lambda a: a.astype(jnp.float32), p)
    eps = arch["rms_norm_eps"]
    x = x + attention_half(_rmsnorm(x, p["attn_norm"], eps), p, arch,
                           operand_dtype)[0]
    y, chosen = ffn_half(_rmsnorm(x, p["ffn_norm"], eps), p, routed, arch,
                         operand_dtype)
    return x + y, chosen


@functools.partial(jax.jit, static_argnames=("routed", "arch",
                                             "operand_dtype"))
def _layer(x, p, routed, arch: tuple, operand_dtype):
    return layer(x, p, routed, dict(arch), operand_dtype)


def layers_of(arch: dict) -> list[bool]:
    """Whether each layer held has routed experts."""
    return [i >= arch["first_k_dense_replace"]
            for i in range(arch["num_hidden_layers"])]


def forward(params, tokens, arch: dict, operand_dtype=jnp.float32):
    """tokens (T,) int32, T a multiple of `ROW_BLOCK` or below it ->
    (logits (T, padded vocab) float32, the experts chosen (expert layers,
    T, k)). One layer's weights in float32 at a time."""
    frozen = tuple(sorted(arch.items()))
    with jax.default_matmul_precision("highest"):
        x = params["wte"][tokens].astype(jnp.float32)
        chosen = []
        for routed, p in zip(layers_of(arch), params["layers"], strict=True):
            x, c = _layer(x, p, routed, frozen, operand_dtype)
            if c is not None:
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
