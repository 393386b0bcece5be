"""Flash-attention kernel vs the einsum reference (interpret mode on
CPU — SURVEY.md §4: pure-logic kernel tests without hardware)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops.attention import causal_attention_reference
from ray_tpu.ops.flash_attention import flash_attention


def _rand_qkv(key, B, T, H, D, dtype=jnp.float32):
    ks = jax.random.split(key, 3)
    shape = (B, T, H, D)
    return tuple(jax.random.normal(k, shape, dtype) for k in ks)


def _reference(q, k, v, causal):
    if causal:
        return causal_attention_reference(q, k, v)
    D = q.shape[-1]
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32)
    probs = jax.nn.softmax(logits / D ** 0.5, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


@pytest.mark.parametrize("T,block", [(256, 128), (128, 128), (256, 64)])
def test_forward_matches_reference(T, block):
    q, k, v = _rand_qkv(jax.random.PRNGKey(0), 2, T, 2, 64)
    out = flash_attention(q, k, v, causal=True, block_q=block, block_k=block,
                          interpret=True)
    ref = causal_attention_reference(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_forward_noncausal():
    q, k, v = _rand_qkv(jax.random.PRNGKey(1), 1, 128, 2, 32)
    out = flash_attention(q, k, v, causal=False, block_q=64, block_k=64,
                          interpret=True)
    ref = _reference(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_gradients_match_reference():
    q, k, v = _rand_qkv(jax.random.PRNGKey(2), 1, 128, 2, 32)

    def loss_flash(q, k, v):
        o = flash_attention(q, k, v, causal=True, block_q=64, block_k=64,
                            interpret=True)
        return jnp.sum(o * jnp.cos(o))

    def loss_ref(q, k, v):
        o = causal_attention_reference(q, k, v)
        return jnp.sum(o * jnp.cos(o))

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g_flash, g_ref, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-5, rtol=5e-4, err_msg=f"d{name}")


def test_bfloat16_inputs():
    q, k, v = _rand_qkv(jax.random.PRNGKey(3), 1, 128, 2, 64, jnp.bfloat16)
    out = flash_attention(q, k, v, causal=True, block_q=64, block_k=64,
                          interpret=True)
    ref = causal_attention_reference(q, k, v)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out, dtype=np.float32),
                               np.asarray(ref, dtype=np.float32),
                               atol=3e-2, rtol=3e-2)


def test_indivisible_seq_raises():
    q, k, v = _rand_qkv(jax.random.PRNGKey(4), 1, 96, 1, 32)
    with pytest.raises(ValueError):
        flash_attention(q, k, v, block_q=64, block_k=64, interpret=True)


# ------------------------------------------- layouts, pairs, default blocks


# (B, T, H, D), causal, dtype: the default tiles everywhere, so at
# T >= 512 a row has skipped, diagonal and unmasked tiles
SHAPE_CASES = {
    "pair-512": ((2, 512, 4, 64), True, jnp.float32),
    "pair-1024": ((1, 1024, 2, 64), True, jnp.float32),
    # 2 x 2 grid blocks of 1,024 rows: one skipped, one whole, two diagonal
    "pair-2048": ((1, 2048, 2, 64), True, jnp.float32),
    "odd-heads-fallback": ((2, 256, 3, 64), True, jnp.float32),
    "one-head-128": ((1, 256, 2, 128), True, jnp.float32),
    "pair-noncausal": ((1, 256, 2, 64), False, jnp.float32),
    "pair-bf16": ((1, 512, 2, 64), True, jnp.bfloat16),
}
# (atol, rtol) of the output and of the gradients
TOLERANCE = {jnp.float32: ((2e-5, 2e-5), (5e-5, 5e-4)),
             jnp.bfloat16: ((3e-2, 3e-2), (6e-2, 6e-2))}


@functools.lru_cache(maxsize=None)
def _outputs_and_gradients(case):
    """{what: (kernel's, reference's)} of a case, computed once for its
    four tests: the output and the three gradients of sum(o * cos(o))."""
    shape, causal, dtype = SHAPE_CASES[case]
    q, k, v = _rand_qkv(jax.random.PRNGKey(5), *shape, dtype)

    def both(attention):
        def loss(q, k, v):
            o = attention(q, k, v)
            o32 = o.astype(jnp.float32)
            return jnp.sum(o32 * jnp.cos(o32)), o

        (_, o), grads = jax.value_and_grad(
            loss, argnums=(0, 1, 2), has_aux=True)(q, k, v)
        return (o, *grads)

    got = both(lambda q, k, v: flash_attention(q, k, v, causal=causal,
                                               interpret=True))
    want = both(lambda q, k, v: _reference(q, k, v, causal))
    return dict(zip(("forward", "dq", "dk", "dv"), zip(got, want)))


@pytest.mark.parametrize("what", ["forward", "dq", "dk", "dv"])
@pytest.mark.parametrize("case", sorted(SHAPE_CASES))
def test_layouts_match_reference(case, what):
    shape, _, dtype = SHAPE_CASES[case]
    got, want = _outputs_and_gradients(case)[what]
    assert got.dtype == dtype and got.shape == shape
    atol, rtol = TOLERANCE[dtype][what != "forward"]
    np.testing.assert_allclose(np.asarray(got, dtype=np.float32),
                               np.asarray(want, dtype=np.float32),
                               atol=atol, rtol=rtol, err_msg=f"{case} {what}")


# T, H, D -> layout, heads a block, then (tile, tiles run, masked, in all)
# of the forward and dk/dv (tiles of 128) and of dq (256, T allowing)
@pytest.mark.parametrize("T,H,D,want", [
    # the train cells: two heads a block, tiles below the sequence
    (1024, 12, 64, ("dense", 2, (128, 36, 8, 64), (256, 10, 4, 16))),
    (512, 12, 64, ("dense", 2, (128, 10, 4, 16), (256, 3, 2, 4))),
    (256, 12, 64, ("dense", 2, (128, 3, 2, 4), (128, 3, 2, 4))),
    (128, 12, 64, ("dense", 2, (128, 1, 1, 1), (128, 1, 1, 1))),
    (1024, 3, 64, ("per_head", 1, (128, 36, 8, 64), (256, 10, 4, 16))),
    (1024, 32, 128, ("dense", 1, (128, 36, 8, 64), (256, 10, 4, 16))),
    (2048, 8, 256, ("dense", 1, (128, 136, 16, 256), (256, 36, 8, 64))),
    (1024, 4, 32, ("per_head", 1, (128, 36, 8, 64), (256, 10, 4, 16))),
    (1536, 12, 64, ("dense", 2, (128, 78, 12, 144), (256, 21, 6, 36))),
])
def test_plan(T, H, D, want):
    from ray_tpu.ops.flash_attention import plan

    p = plan(T, H, D)
    layout, heads, small, large = want
    assert (p["layout"], p["heads_per_block"]) == (layout, heads)
    for kernel, (block, run, masked, grid) in (
            ("fwd", small), ("dq", large), ("dkv", small)):
        steps = p["kernels"][kernel]
        assert (steps["block_q"], steps["block_k"]) == (block, block)
        assert steps["grid_rows"] == min(
            T, 1024 if T % 1024 == 0 else 512)
        assert (steps["steps_run"], steps["steps_masked"],
                steps["steps_in_grid"]) == (run, masked, grid)
        assert steps["run_share"] == run / grid
        assert steps["run_share"] < 1 or T == 128
    assert plan(T, H, D, causal=False)["kernels"]["fwd"]["run_share"] == 1
    one = plan(T, H, D, block_q=128, block_k=128)["kernels"]
    assert one["fwd"] == one["dq"] == one["dkv"]


def test_mask_only_on_the_diagonal_changes_no_bit():
    """Blocks below the diagonal run unmasked; masking them as well, as
    the kernels once did, must give the same bits."""
    import dataclasses

    from ray_tpu.ops import flash_attention as fa

    B, T, H, D = 1, 512, 2, 64
    q, k, v = (t.reshape(B, T, H * D)
               for t in _rand_qkv(jax.random.PRNGKey(6), B, T, H, D))
    # 2 x 2 grid blocks of 2 x 2 tiles: whole grid blocks below the
    # diagonal, and tiles below it inside the grid blocks it crosses
    cfg = fa._Cfg(causal=True, sm_scale=D ** -0.5, block_q=128, block_k=128,
                  interpret=True, head_dim=D, heads=2, grid_rows=256)
    everywhere = dataclasses.replace(cfg, mask_every_block=True)
    o, lse = fa._fwd(q, k, v, cfg)
    o_all, lse_all = fa._fwd(q, k, v, everywhere)
    cfg, everywhere = (cfg,) * 3, (everywhere,) * 3
    np.testing.assert_array_equal(np.asarray(o), np.asarray(o_all))
    np.testing.assert_array_equal(np.asarray(lse), np.asarray(lse_all))
    do = jax.random.normal(jax.random.PRNGKey(7), o.shape, o.dtype)
    for a, b in zip(fa._bwd(q, k, v, o, lse, do, cfg),
                    fa._bwd(q, k, v, o, lse, do, everywhere)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_indivisible_default_blocks_raise():
    q, k, v = _rand_qkv(jax.random.PRNGKey(4), 1, 200, 2, 64)
    with pytest.raises(ValueError):
        flash_attention(q, k, v, interpret=True)
