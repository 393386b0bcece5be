"""The mimo_v2 stack, plainly: the published forward pass in float32
`jax.numpy`, written from the model's description (XiaomiMiMo/MiMo-V2.5,
`model_type` mimo_v2) and not from `ray_tpu/models/mimo_v2.py`.

No kernels, no cache, no tiles, no batching, no dispatch: a Python loop
over the layers; attention as full-length score matrices (T x T a head)
with the window as a mask and the sink as one more column, dropped after
the softmax; in an expert layer EVERY held expert computed for EVERY
token and combined with the routing weights, which are zero for the
experts a token did not choose. One layer's weights are cast up to
float32 at a time, so that the whole model never exists in float32.

Every block is ``x = x + attn(rmsnorm(x)); x = x + ffn(rmsnorm(x))`` (eps
`layernorm_epsilon`), the two halves given by the block's entries in
`hybrid_layer_pattern` and `moe_layer_freq`:

  attention, pattern 0 (full) or 1 (window):
      q, k, v = h Wq, h Wk, h Wv (no bias); 64 query heads; HK KV heads
          (`num_key_value_heads` full, `swa_num_key_value_heads` window);
          q and k heads `head_dim` wide, v heads `v_head_dim`
      the first R = int(partial_rotary_factor * head_dim) dimensions of
          every q and k head are rotated by the position t: pairs
          (i, i + R/2), angle t * theta^(-2i/R), theta `rope_theta`
          (full) or `swa_rope_theta` (window); the others are not
      z[t,s] = q_t . k_s / sqrt(head_dim), query head i reading K/V head
          i // (64 / HK); s <= t, and in a window layer also
          s > t - sliding_window (the position itself included)
      p[t,s] = exp(z[t,s]) / (exp(sink_i) + sum_s' exp(z[t,s']))  in a
          layer that has sinks (window layers: `add_swa_attention_sink_
          bias`), the plain softmax in one that has none
      a_t = sum_s p[t,s] (attention_value_scale * v_s) ; out = a Wo
  feed-forward, freq 0 (dense) or 1 (routed experts):
      dense: down(silu(gate(h)) * up(h)), width `intermediate_size`
      experts: s = sigmoid_float32(h Wrouter) over ALL experts
          the k largest of s + e_score_correction_bias are chosen
          their weights: s at the chosen (without the bias), divided by
              their sum (`norm_topk_prob`); `routed_scaling_factor` null
              is 1
          out = sum over the HELD experts e of
                weight[e] * down_e(silu(gate_e(h)) * up_e(h))
          (no shared expert)
  logits = rmsnorm(x) Whead                      (head not tied)

The share: this chip holds the experts its weights stack, from
`expert_offset` on. What the absent experts would have added is left out,
here as in the program; the vocabulary is the held slice.

Assumed (each also listed in the configuration file): there is no q/k
norm (the config has no key for one); the value scale applies in both
kinds of layer; the window includes the position itself;
`attention_chunk_size` (equal to the window) is not read; `n_group` 1 and
`topk_group` 1 make the group-limited choice the plain one; the MTP
layers and the vision and audio towers are not part of this model. The
weights are the program's own pytree (one dict a layer). Ties among
router scores break as `lax.top_k` does (the lower index).

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
                       "configs", "mimo-v2.5.json")
ARCH_KEYS = ("hybrid_layer_pattern", "moe_layer_freq", "num_hidden_layers",
             "num_attention_heads", "num_key_value_heads",
             "swa_num_key_value_heads", "head_dim", "v_head_dim",
             "partial_rotary_factor", "rope_theta", "swa_rope_theta",
             "sliding_window", "attention_value_scale",
             "add_swa_attention_sink_bias", "add_full_attention_sink_bias",
             "num_experts_per_tok", "norm_topk_prob",
             "routed_scaling_factor", "expert_offset", "layernorm_epsilon",
             "vocab_size")


def published_arch() -> dict:
    """The keys of the published config this reference needs, from the
    benchmark's configuration file (lists as tuples: hashable)."""
    with open(_CONFIG) as f:
        config = json.load(f)
    return {k: tuple(config[k]) if isinstance(config[k], list) else config[k]
            for k in ARCH_KEYS}


def layers_of(arch: dict) -> list[tuple[int, int]]:
    """(attention pattern, feed-forward freq) of each layer held."""
    n = arch["num_hidden_layers"]
    return list(zip(arch["hybrid_layer_pattern"][:n],
                    arch["moe_layer_freq"][:n]))


def _rmsnorm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * weight


def _rotate(x, theta: float, width: int):
    """x (T, heads, D): the first `width` dimensions of every head rotated
    by the row's position, pairs (i, i + width / 2)."""
    T = x.shape[0]
    half = width // 2
    angle = jnp.arange(T, dtype=jnp.float32)[:, None] \
        * theta ** (-2.0 * jnp.arange(half, dtype=jnp.float32) / width)
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    a, b = x[..., :half], x[..., half:width]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos,
                            x[..., width:]], axis=-1)


def attention(h, p, window_layer: bool, arch: dict, mm, lo):
    """One attention half on its normed rows h (T, hidden)."""
    T = h.shape[0]
    Hq, D, Dv = (arch["num_attention_heads"], arch["head_dim"],
                 arch["v_head_dim"])
    HK = arch["swa_num_key_value_heads" if window_layer
              else "num_key_value_heads"]
    theta = arch["swa_rope_theta" if window_layer else "rope_theta"]
    rotated = int(arch["partial_rotary_factor"] * D)
    q = _rotate(mm(h, p["wq"]).reshape(T, Hq, D), theta, rotated)
    k = _rotate(mm(h, p["wk"]).reshape(T, HK, D), theta, rotated)
    v = mm(h, p["wv"]).reshape(T, HK, Dv) * arch["attention_value_scale"]
    k = jnp.repeat(k, Hq // HK, axis=1)
    v = jnp.repeat(v, Hq // HK, axis=1)
    z = jnp.einsum("qhd,khd->hqk", lo(q), lo(k)) \
        / np.sqrt(arch.get("score_width", D))
    ahead = jnp.arange(T)[:, None] - jnp.arange(T)[None, :]  # t - s
    seen = ahead >= 0
    if window_layer and arch["sliding_window"] is not None:
        seen = seen & (ahead < arch["sliding_window"])
    z = jnp.where(seen, z, -jnp.inf)
    sinks = arch["add_swa_attention_sink_bias" if window_layer
                 else "add_full_attention_sink_bias"]
    if sinks:  # one more column, which has no value
        column = jnp.broadcast_to(p["sink"][:, None, None], (Hq, T, 1))
        prob = jax.nn.softmax(jnp.concatenate([z, column], -1), -1)[..., :T]
    else:
        prob = jax.nn.softmax(z, axis=-1)
    a = jnp.einsum("hqk,khd->qhd", lo(prob), lo(v))
    return mm(a.reshape(T, Hq * Dv), p["wo"])


def dense(h, p, mm):
    return mm(jax.nn.silu(mm(h, p["w_gate"])) * mm(h, p["w_up"]),
              p["w_down"])


def experts(h, p, arch: dict, lo):
    """-> (the held experts' part of the routed sum, the experts each
    token chose (T, k))."""
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
    return jnp.einsum("te,etd->td", weights, out), chosen


def _lower(operand_dtype):
    def lo(a):
        return a.astype(operand_dtype).astype(jnp.float32)

    return lo, (lambda a, w: lo(a) @ lo(w))


def attention_half(h, p, window_layer: bool, arch: dict,
                   operand_dtype=jnp.float32):
    """What a block's attention adds to x, from its normed rows h (T,
    hidden) and that layer's weights in float32. `operand_dtype` below
    float32 rounds every matrix product's operands to it first: the same
    mathematics "computed in a lower precision", for the readings that set
    a tolerance; the reference itself never uses it."""
    lo, mm = _lower(operand_dtype)
    return attention(h, p, window_layer, arch, mm, lo)


def ffn_half(h, p, routed: bool, arch: dict, operand_dtype=jnp.float32):
    """What a block's feed-forward adds to x -> (y, the experts each token
    chose (T, k) or None)."""
    lo, mm = _lower(operand_dtype)
    if routed:
        return experts(h, p, arch, lo)
    return dense(h, p, mm), None


def layer(x, p, window_layer: bool, routed: bool, arch: dict,
          operand_dtype=jnp.float32):
    """One block on x (T, hidden), `p` that layer's weights. Returns (x,
    the experts each token chose (T, k), or None)."""
    p = jax.tree.map(lambda a: a.astype(jnp.float32), p)
    eps = arch["layernorm_epsilon"]
    x = x + attention_half(_rmsnorm(x, p["attn_norm"], eps), p, window_layer,
                           arch, operand_dtype)
    y, chosen = ffn_half(_rmsnorm(x, p["ffn_norm"], eps), p, routed, arch,
                         operand_dtype)
    return x + y, chosen


@functools.partial(jax.jit, static_argnames=(
    "window_layer", "routed", "arch", "operand_dtype"))
def _layer(x, p, window_layer, routed, arch: tuple, operand_dtype):
    return layer(x, p, window_layer, routed, dict(arch), operand_dtype)


def forward(params, tokens, arch: dict, operand_dtype=jnp.float32):
    """tokens (T,) int32 -> (logits (T, padded vocab) float32, the experts
    chosen (expert layers, T, k)). One layer's weights in float32 at a
    time."""
    frozen = tuple(sorted(arch.items()))
    with jax.default_matmul_precision("highest"):
        x = params["wte"][tokens].astype(jnp.float32)
        chosen = []
        for (kind, routed), p in zip(layers_of(arch), params["layers"],
                                     strict=True):
            x, c = _layer(x, p, bool(kind), bool(routed), frozen,
                          operand_dtype)
            if c is not None:
                chosen.append(c)
        x = _rmsnorm(x, params["lnf"].astype(jnp.float32),
                     arch["layernorm_epsilon"])
        logits = x @ params["lm_head"].astype(jnp.float32)
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
    sequence (teacher forcing; no cache), with the very weights the engine
    serves. `model` carries the harness's five sizes; what this family
    needs beyond them it reads from its configuration file. Sequences are
    padded at the end to a multiple of `pad_to`, which a causal model's
    earlier positions cannot see, so few programs serve all. `arch` and
    the dtype are for the controls (another share, a lower precision, a
    mechanism left out), which must NOT pass the check."""
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
