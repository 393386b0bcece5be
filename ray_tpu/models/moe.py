"""Routed experts: top-k gating in which no token is dropped.

One implementation serves the llama-family MoE block (OLMoE: 64 SwiGLU
experts, 8 a token, softmax), the nemotron_h block (128 relu2 experts of
which a chip holds a share, 6 a token, sigmoid with a selection bias, one
shared expert), the mimo_v2, glm_dsa and lfm2 blocks (SwiGLU experts of
which a chip holds a share, sigmoid with a selection bias) and the
stand-alone `moe_layer` (GELU experts):

- **route** (`moe.route`): router logits and scores (softmax over all
  experts, or a sigmoid each) in float32, `lax.top_k` (of the scores plus
  a selection bias where the router has one; where the router has a group
  limit, `n_group`, among the experts of its `topk_group` best groups
  alone), weights renormalised only when asked, and the count of pairs per
  expert, over ALL experts;
- **dispatch** (`moe.dispatch`): the routing weights as an (N, E) matrix,
  zero where a token did not choose an expert, cut to the experts held
  here (`held`) where the stacked weights are a share of the router's;
- **experts** (`moe.experts`): every expert computes every row, as batched
  products over the stacked weights;
- **combine** (`moe.combine`): the matrix picks the chosen pairs out and
  sums them; an always-on expert (`shared`, `moe.shared`) is added.

So every chosen pair is computed whatever the imbalance — there is no
capacity — and nothing is sorted, gathered or scattered. The arithmetic
is E / k times the chosen pairs'. That is the faster way on the v5e at
every row count a serve program has, for two measured reasons (PERF.md,
PR 27; one OLMoE layer, 805 MB of experts): from 16 rows on every expert
has rows and the layer takes as long as reading the weights (1.22-1.40 ms
at 16-256 rows, where `jax.lax.ragged_dot` over rows ordered by expert
takes 1.22-2.85 ms and the Pallas `megablox` product 1.24-1.90 ms); and
below that, where a grouped product alone is faster (0.39 ms at one row),
a grouped kernel is a custom call, for which XLA copies each layer's
experts out of the scanned stack first (three copies of 268 MB a layer).
Measured again at the nemotron_h cut's widths (PERF.md, PR 32; one layer,
32 held experts of 2688 x 1856 x 2, 639 MB, 6 of 128 a token, so a row
has 1.5 pairs here and every-expert is 21 times the chosen pairs'
arithmetic): 0.87 / 0.88 / 0.90 / 0.92 / 1.01 ms at 8 / 32 / 64 / 128 /
256 rows against 2.45 / 3.12 / 4.38 / 5.25 / 6.18 ms for `ragged_dot`
over the pairs ordered by expert, with no stack to copy from there: the
same way wins at every row count a serve program has, by more, so the
program still chooses nothing.

The per-expert pair counts leave the layer with its output: imbalance is
what a router costs, and only the program can see it.

Expert weights carry the `expert` mesh axis on their expert dimension
(`moe_partition_rules`, and the llama rules for `we_*`); GSPMD partitions
the batched products from there.
"""

from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def route(x: jax.Array, router: jax.Array, k: int, norm_topk: bool, *,
          score: str = "softmax", select_bias: jax.Array | None = None,
          scale: float = 1.0, norm_eps: float = 0.0, n_group: int = 1,
          topk_group: int = 1):
    """x (N, Dm), router (Dm, E) -> (weights (N, k) f32, experts (N, k)
    i32, pairs per expert (E,) i32, scores (N, E) f32). `score` is
    "softmax" (over ALL experts) or "sigmoid" (each expert for itself);
    the k largest of score + `select_bias` (E,) are chosen, and their
    weights are the scores WITHOUT the bias, summing to one only when
    `norm_topk` (divided by their sum plus `norm_eps`, which a family
    whose published router has one gives: lfm2's 1e-6), times `scale`.
    `n_group` > 1 is DeepSeek-V3's group limit (`noaux_tc`): the experts
    are `n_group` groups of neighbours, a group's score the sum of its
    two largest biased scores, and only the experts of the `topk_group`
    best groups can be chosen."""
    with jax.named_scope("moe.route"):
        logits = x.astype(jnp.float32) @ router.astype(jnp.float32)
        if score == "softmax":
            probs = jax.nn.softmax(logits, axis=-1)
        elif score == "sigmoid":
            probs = jax.nn.sigmoid(logits)
        else:
            raise ValueError(f"unknown router score {score!r}")
        if select_bias is None and n_group == 1:
            weights, experts = jax.lax.top_k(probs, k)
        else:
            biased = probs if select_bias is None \
                else probs + select_bias.astype(jnp.float32)
            if n_group > 1:
                biased = _within_best_groups(biased, n_group, topk_group)
            _, experts = jax.lax.top_k(biased, k)
            weights = jnp.take_along_axis(probs, experts, axis=-1)
        if norm_topk:
            total = jnp.sum(weights, axis=-1, keepdims=True)
            if norm_eps:
                total = total + norm_eps
            weights = weights / total
        if scale != 1.0:
            weights = weights * scale
        counts = jnp.zeros((router.shape[-1],), jnp.int32).at[
            experts.reshape(-1)].add(1)
    return weights, experts, counts, probs


def _within_best_groups(biased, n_group: int, topk_group: int):
    """Biased scores (N, E) with -inf outside each row's `topk_group`
    best of `n_group` groups of E / n_group neighbouring experts, a
    group scored by the sum of its two largest."""
    N, E = biased.shape
    groups = biased.reshape(N, n_group, E // n_group)
    best_two = jnp.sum(jax.lax.top_k(groups, 2)[0], axis=-1)
    _, kept = jax.lax.top_k(best_two, topk_group)
    keep = jnp.zeros((N, n_group), bool).at[
        jnp.arange(N)[:, None], kept].set(True)
    return jnp.where(keep[:, :, None], groups, -jnp.inf).reshape(N, E)


def routed_experts(
    x: jax.Array,
    router: jax.Array,
    expert_fn: Callable,
    *,
    k: int,
    norm_topk: bool,
    score: str = "softmax",
    select_bias: jax.Array | None = None,
    scale: float = 1.0,
    held: tuple[int, int] | None = None,
    shared: Callable | None = None,
    norm_eps: float = 0.0,
    n_group: int = 1,
    topk_group: int = 1,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """x (N, Dm) -> (out (N, Dm), pairs per expert (E,) i32, router scores
    (N, E) f32). ``expert_fn(rows, mm)`` is one expert's feed-forward
    written for all experts at once: ``mm(a, w)`` multiplies rows ``a``
    ((N, in) going in, (E, N, in) between the layers) with the stacked
    weights ``w`` (E, in, out), expert by expert.

    `held` = (offset, count) says which of the router's E experts the
    stacked weights are: the routing and the pairs per expert are over all
    E (the router's load is the model's, whatever is held), the result is
    the part the held experts give, and what the absent ones would have
    added is left out. ``shared(x) -> (N, Dm)`` is an expert every row
    goes through, added to the routed sum. `norm_eps`, `n_group` and
    `topk_group` are `route`'s."""
    N = x.shape[0]
    weights, experts, counts, probs = route(
        x, router, k, norm_topk, score=score, select_bias=select_bias,
        scale=scale, norm_eps=norm_eps, n_group=n_group,
        topk_group=topk_group)
    with jax.named_scope("moe.dispatch"):
        per_expert = jnp.zeros((N, router.shape[-1]), weights.dtype).at[
            jnp.arange(N)[:, None], experts].set(weights)
        if held is not None and held != (0, router.shape[-1]):
            per_expert = per_expert[:, held[0]:held[0] + held[1]]
    with jax.named_scope("moe.experts"):
        y = expert_fn(x, lambda a, w: jnp.einsum(
            "nd,edf->enf" if a.ndim == 2 else "end,edf->enf", a, w))
    with jax.named_scope("moe.combine"):
        out = jnp.einsum("ne,end->nd", per_expert.astype(y.dtype), y)
    if shared is not None:
        with jax.named_scope("moe.shared"):
            out = out + shared(x).astype(out.dtype)
    return out.astype(x.dtype), counts, probs


# --------------------------------------------------------------------------
# The stand-alone layer (GELU experts, weights renormalised over the chosen
# k, Switch-style auxiliary loss): the training-side user of the above.


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    num_experts: int = 8
    top_k: int = 2
    d_model: int = 128
    d_ff: int = 512
    dtype: object = jnp.bfloat16


def init_moe(key: jax.Array, cfg: MoEConfig) -> dict:
    kg, k1, k2 = jax.random.split(key, 3)
    E, Dm, Df = cfg.num_experts, cfg.d_model, cfg.d_ff
    s1 = (2.0 / Dm) ** 0.5
    s2 = (2.0 / Df) ** 0.5
    return {
        "gate": {"kernel": jax.random.normal(kg, (Dm, E)) * 0.02},
        "wi": jax.random.normal(k1, (E, Dm, Df)) * s1,  # expert-sharded
        "wo": jax.random.normal(k2, (E, Df, Dm)) * s2,
    }


def moe_partition_rules() -> list[tuple[str, P]]:
    """Merge into a model's PartitionRules: expert weights shard their
    leading (expert) dim on the `expert` axis, ff dim on `tensor`."""
    return [
        (r"moe/wi$", P("expert", "fsdp", "tensor")),
        (r"moe/wo$", P("expert", "tensor", "fsdp")),
        (r"moe/gate/kernel$", P(None, None)),
    ]


def moe_layer(params: dict, x: jax.Array, cfg: MoEConfig,
              ) -> tuple[jax.Array, jax.Array]:
    """x: (B, T, Dm) -> (out (B, T, Dm), aux_loss scalar)."""
    B, T, Dm = x.shape
    E = cfg.num_experts
    wi = params["wi"].astype(cfg.dtype)
    wo = params["wo"].astype(cfg.dtype)
    out, counts, probs = routed_experts(
        x.reshape(B * T, Dm).astype(cfg.dtype), params["gate"]["kernel"],
        lambda rows, mm: mm(jax.nn.gelu(mm(rows, wi)), wo),
        k=cfg.top_k, norm_topk=True)
    # Switch-style load balancing aux loss: E * sum_e f_e * p_e
    frac_tokens = counts.astype(jnp.float32) / (B * T * cfg.top_k)
    aux = E * jnp.sum(frac_tokens * jnp.mean(probs, axis=0))
    return out.reshape(B, T, Dm).astype(x.dtype), aux
