"""GPT-2, plainly: the published forward pass in float32 `jax.numpy`.

No kernels, no KV cache, no scan, no remat, no batching tricks; a Python
loop over the layers. Departures from the published model: none in the
mathematics (pre-LN blocks, learned positions, tanh GELU, tied output
head); the weights are the program's own pytree (blocks stacked along a
leading layer axis, vocabulary rows padded to a multiple of 128), whose
padded rows are masked out of every softmax.

On a TPU a float32 matmul runs in lower precision unless asked otherwise,
so every entry point runs under `jax.default_matmul_precision("highest")`.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def _layer_norm(x, scale, bias, eps=1e-5):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * scale + bias


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        np.sqrt(2.0 / np.pi) * (x + 0.044715 * x ** 3)))


def forward(params, tokens, n_head: int):
    """tokens (B, T) int32 -> logits (B, T, padded vocab) float32."""
    f32 = lambda t: jax.tree.map(lambda a: a.astype(jnp.float32), t)  # noqa: E731
    params = f32(params)
    B, T = tokens.shape
    x = params["wte"][tokens] + params["wpe"][:T]
    E = x.shape[-1]
    D = E // n_head
    blocks = params["blocks"]
    n_layer = blocks["attn_qkv"]["kernel"].shape[0]
    causal = jnp.tril(jnp.ones((T, T), bool))
    for i in range(n_layer):
        p = jax.tree.map(lambda a: a[i], blocks)
        h = _layer_norm(x, p["ln1"]["scale"], p["ln1"]["bias"])
        qkv = h @ p["attn_qkv"]["kernel"] + p["attn_qkv"]["bias"]
        q, k, v = (t.reshape(B, T, n_head, D)
                   for t in jnp.split(qkv, 3, axis=-1))
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(D)
        s = jnp.where(causal, s, -jnp.inf)
        a = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)
        a = a.reshape(B, T, E) @ p["attn_proj"]["kernel"] \
            + p["attn_proj"]["bias"]
        x = x + a
        h = _layer_norm(x, p["ln2"]["scale"], p["ln2"]["bias"])
        h = _gelu_tanh(h @ p["mlp_fc"]["kernel"] + p["mlp_fc"]["bias"])
        x = x + h @ p["mlp_proj"]["kernel"] + p["mlp_proj"]["bias"]
    x = _layer_norm(x, params["lnf"]["scale"], params["lnf"]["bias"])
    return x @ params["wte"].T


def _log_softmax(logits, vocab_size: int):
    mask = jnp.arange(logits.shape[-1]) < vocab_size
    return jax.nn.log_softmax(jnp.where(mask, logits, -jnp.inf), axis=-1)


def loss_sum(params, tokens, targets, n_head: int, vocab_size: int):
    """Sum of next-token cross entropies over a (B, T) batch."""
    with jax.default_matmul_precision("highest"):
        logp = _log_softmax(forward(params, tokens, n_head), vocab_size)
        return -jnp.sum(jnp.take_along_axis(
            logp, targets[..., None], axis=-1))


def mean_loss(params, tokens, targets, model: dict, rows: int = 2) -> float:
    """Mean cross entropy of a batch, `rows` sequences at a time (the
    float32 logits of a whole batch do not fit beside a training state)."""
    fn = jax.jit(loss_sum, static_argnums=(3, 4))
    total = 0.0
    for i in range(0, tokens.shape[0], rows):
        total += float(fn(params, tokens[i:i + rows], targets[i:i + rows],
                          model["n_head"], model["vocab_size"]))
    return total / float(tokens.shape[0] * tokens.shape[1])


def serve_reference(params, model: dict, cases: list[dict],
                    pad_to: int = 64) -> list[list[float]]:
    """For the serve cells: log p(tokens[i] | prompt + tokens[:i]) of each
    case's streamed tokens, by one full forward pass over the whole
    sequence (teacher forcing; no cache), with the very weights the engine
    serves. Sequences are padded at the end to one length, which a causal
    model's earlier positions cannot see, so one program serves all."""
    longest = max(len(c["prompt"]) + len(c["tokens"]) for c in cases)
    width = -(-longest // pad_to) * pad_to
    seqs = np.zeros((len(cases), width), np.int32)
    for i, c in enumerate(cases):
        seq = list(c["prompt"]) + list(c["tokens"])
        seqs[i, :len(seq)] = seq

    def logp_of(p, row):
        with jax.default_matmul_precision("highest"):
            return _log_softmax(forward(p, row[None], model["n_head"]),
                                model["vocab_size"])[0]

    fn = jax.jit(logp_of)
    out = []
    for i, c in enumerate(cases):
        logp = np.asarray(fn(params, seqs[i]))
        n = len(c["prompt"])
        out.append([float(logp[n - 1 + j, t])
                    for j, t in enumerate(c["tokens"])])
    return out
