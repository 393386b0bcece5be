"""`ops.cross_entropy` against `log_softmax` + `take_along_axis` written
out: the value and the gradient with respect to the logits."""

import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops.cross_entropy import cross_entropy

# the train cells' vocabulary, 50,304 columns of which 50,257 are real,
# scaled down by 64
V, V_REAL = 786, 785


def _by_gather(logits, targets, vocab_size, weights):
    """What the four loss heads did before PR 45."""
    logits = logits.astype(jnp.float32)
    if vocab_size is not None:
        logits = jnp.where(jnp.arange(logits.shape[-1]) < vocab_size,
                           logits, -1e9)
    logp = jax.nn.log_softmax(logits, axis=-1)
    ll = jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    if weights is None:
        return -jnp.mean(ll)
    return -jnp.sum(ll * weights) / jnp.maximum(jnp.sum(weights), 1.0)


CASES = list(itertools.product(
    ["float32", "bfloat16"], [False, True], [False, True], [(96,), (6, 16)]))


@pytest.mark.parametrize(
    "dtype,padded,weighted,lead", CASES,
    ids=[f"{d}-{'padded' if p else 'whole'}-{'weighted' if w else 'mean'}-"
         f"{len(s) + 1}d" for d, p, w, s in CASES])
def test_matches_log_softmax_and_gather(dtype, padded, weighted, lead):
    rng = np.random.RandomState(len(lead) + 2 * padded + 4 * weighted)
    real = V_REAL if padded else V
    targets = rng.randint(0, real, size=lead)
    # a model some way into training: the target's logit stands out, so
    # the loss is near 1 and 1e-6 is several float32 steps of it (at the
    # 11 nats of uniform guessing one step is 9.5e-7)
    logits = 2.0 * rng.normal(size=lead + (V,))
    np.put_along_axis(logits, targets[..., None], 8.0, axis=-1)
    logits = jnp.asarray(logits, dtype)
    targets = jnp.asarray(targets, jnp.int32)
    vocab_size = real if padded else None
    weights = (jnp.asarray(rng.uniform(size=lead) < 0.7, jnp.float32)
               if weighted else None)

    got, got_grad = jax.jit(jax.value_and_grad(
        lambda x: cross_entropy(x, targets, vocab_size=vocab_size,
                                weights=weights)))(logits)
    want, want_grad = jax.jit(jax.value_and_grad(
        lambda x: _by_gather(x, targets, vocab_size, weights)))(logits)

    assert got.dtype == jnp.float32 and got.shape == ()
    assert abs(float(got) - float(want)) < 1e-6
    assert got_grad.dtype == logits.dtype
    # bf16 gradients are the float32 ones rounded: a step of bf16 at most
    tolerance = 1e-6 if dtype == "float32" else 2.0 ** -8 * float(
        jnp.max(jnp.abs(want_grad.astype(jnp.float32))))
    np.testing.assert_allclose(np.asarray(got_grad, np.float32),
                               np.asarray(want_grad, np.float32),
                               rtol=0, atol=tolerance)
    if padded:  # a masked column takes no gradient and gives none
        assert not np.asarray(got_grad, np.float32)[..., real:].any()


def test_gradient_is_softmax_minus_onehot():
    rng = np.random.RandomState(7)
    logits = jnp.asarray(rng.normal(size=(5, V)), jnp.float32)
    targets = jnp.asarray(rng.randint(0, V, size=(5,)), jnp.int32)
    grad = jax.grad(lambda x: cross_entropy(x, targets))(logits)
    want = (jax.nn.softmax(logits) - jax.nn.one_hot(targets, V)) / 5
    np.testing.assert_allclose(np.asarray(grad), np.asarray(want),
                               rtol=0, atol=1e-7)
