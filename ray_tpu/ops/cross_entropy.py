"""Next-token cross entropy from the logits, by reductions alone.

`log_softmax` followed by `take_along_axis` makes XLA write the shifted
logits out in float32 at the logits' full shape, because a gather cannot
fuse with the producer of its operand: 6.6 GB a step at 32 x 1024 x 50304
to look up 32,768 numbers (PERF.md section 6, PR 45). Here the target's
logit is picked by comparing an iota with the target and summing over the
vocabulary, and `logsumexp` is a max and a sum: every consumer of the
logits is a reduction that fuses with their producer, and with the
vocabulary sharded each partitions as a partial sum and one all-reduce of
`(...)` floats.

All arithmetic is float32 whatever dtype the logits come in: they are
upcast inside the reductions.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# What a column past the real vocabulary is masked to.
_MASKED = -1e9


def cross_entropy(logits: jax.Array, targets: jax.Array, *,
                  vocab_size: int | None = None,
                  weights: jax.Array | None = None) -> jax.Array:
    """Mean of `-(logit[target] - logsumexp(logits))` over the rows.

    logits: `(..., V)` of any float dtype; targets: `(...)` integers in
    `[0, V)` (a target outside it picks no logit: its row contributes
    `logsumexp` alone). `vocab_size`: the count of real entries where `V`
    is padded; columns at or past it are masked to -1e9. `weights`:
    `(...)`; the result is then `sum(nll * w) / max(sum(w), 1)`.
    """
    x = logits.astype(jnp.float32)
    columns = jnp.arange(x.shape[-1])
    if vocab_size is not None:
        x = jnp.where(columns < vocab_size, x, _MASKED)
    # one non-zero term a row, so the sum is exact
    picked = jnp.sum(
        jnp.where(columns == targets[..., None], x, 0.0), axis=-1)
    nll = jax.nn.logsumexp(x, axis=-1) - picked
    if weights is None:
        return jnp.mean(nll)
    return jnp.sum(nll * weights) / jnp.maximum(jnp.sum(weights), 1.0)
