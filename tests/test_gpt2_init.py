"""`init_gpt2` is one jitted program (PR 62), and its leaves are the ones
the eager draws gave, to the bit: every seeded expectation of the repo
(the train cells' `first_loss`, the serve cells' reference log-probs, the
pinned greedy ids of `tests/test_packed_launch.py`) rests on that.

The eager body it replaced is kept here as the reference."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models.gpt2 import (
    GPT2Config, gpt2_partition_rules, init_gpt2)
from ray_tpu.parallel.mesh import build_mesh
from ray_tpu.parallel.sharding import path_str

CONFIGS = {
    "tiny": GPT2Config.tiny(),
    # nothing a multiple of anything: 3 layers, a vocabulary of 257
    # padded to 384
    "odd": GPT2Config(vocab_size=257, n_layer=3, n_head=4, n_embd=64,
                      block_size=48),
}


def eager_init_gpt2(key, cfg):
    """`init_gpt2` as it was before PR 62: a matrix an eager call, a
    stacked leaf a `jnp.stack` of its layers."""
    k = jax.random.split(key, 8)
    L, E, V = cfg.n_layer, cfg.n_embd, cfg.padded_vocab
    std = 0.02
    resid_std = 0.02 / math.sqrt(2 * cfg.n_layer)

    def dense(kk, in_dim, out_dim, scale):
        return jax.random.normal(kk, (in_dim, out_dim), jnp.float32) * scale

    def stack(idx, initializer):
        keys = jax.random.split(jax.random.fold_in(k[7], idx), L)
        return jnp.stack([initializer(keys[i]) for i in range(L)])

    blocks = {
        "ln1": {"scale": jnp.ones((L, E)), "bias": jnp.zeros((L, E))},
        "attn_qkv": {
            "kernel": stack(0, lambda kk: dense(kk, E, 3 * E, std)),
            "bias": jnp.zeros((L, 3 * E))},
        "attn_proj": {
            "kernel": stack(1, lambda kk: dense(kk, E, E, resid_std)),
            "bias": jnp.zeros((L, E))},
        "ln2": {"scale": jnp.ones((L, E)), "bias": jnp.zeros((L, E))},
        "mlp_fc": {
            "kernel": stack(2, lambda kk: dense(kk, E, 4 * E, std)),
            "bias": jnp.zeros((L, 4 * E))},
        "mlp_proj": {
            "kernel": stack(3, lambda kk: dense(kk, 4 * E, E, resid_std)),
            "bias": jnp.zeros((L, E))},
    }
    return {
        "wte": jax.random.normal(k[0], (V, E), jnp.float32) * std,
        "wpe": jax.random.normal(k[1], (cfg.block_size, E),
                                 jnp.float32) * std,
        "blocks": blocks,
        "lnf": {"scale": jnp.ones((E,)), "bias": jnp.zeros((E,))},
    }


def assert_same_bits(got, want):
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree.leaves(want)):
        name = path_str(path)
        assert a.shape == b.shape and a.dtype == b.dtype, name
        np.testing.assert_array_equal(
            np.asarray(a).view(np.uint32), np.asarray(b).view(np.uint32),
            err_msg=name)


@pytest.fixture(scope="module", params=sorted(CONFIGS))
def cfg(request):
    return CONFIGS[request.param]


@pytest.fixture(scope="module")
def reference(cfg):
    return eager_init_gpt2(jax.random.PRNGKey(3), cfg)


def test_jitted_init_equals_the_eager_draws(cfg, reference):
    assert_same_bits(init_gpt2(jax.random.PRNGKey(3), cfg), reference)


def test_under_an_outer_jit_with_out_shardings(cfg, reference):
    """As the trainer calls it (`init_sharded_state`, the train cells):
    inside a jit of its own that places every leaf on the mesh."""
    mesh = build_mesh({"data": 2, "fsdp": 2, "tensor": 2})
    abstract = jax.eval_shape(init_gpt2, jax.random.PRNGKey(3), cfg)
    shardings = gpt2_partition_rules().shardings(abstract, mesh)
    with jax.set_mesh(mesh):
        params = jax.jit(lambda: init_gpt2(jax.random.PRNGKey(3), cfg),
                         out_shardings=shardings)()
    assert all(len(leaf.sharding.device_set) == 8
               for leaf in jax.tree.leaves(params))
    assert_same_bits(params, reference)


def test_tree_is_what_the_partition_rules_expect(cfg):
    L, E, V = cfg.n_layer, cfg.n_embd, cfg.padded_vocab
    params = jax.eval_shape(init_gpt2, jax.random.PRNGKey(0), cfg)
    shapes = {path_str(path): leaf.shape for path, leaf in
              jax.tree_util.tree_leaves_with_path(params)}
    assert shapes == {
        "wte": (V, E), "wpe": (cfg.block_size, E),
        "blocks/ln1/scale": (L, E), "blocks/ln1/bias": (L, E),
        "blocks/attn_qkv/kernel": (L, E, 3 * E),
        "blocks/attn_qkv/bias": (L, 3 * E),
        "blocks/attn_proj/kernel": (L, E, E),
        "blocks/attn_proj/bias": (L, E),
        "blocks/ln2/scale": (L, E), "blocks/ln2/bias": (L, E),
        "blocks/mlp_fc/kernel": (L, E, 4 * E),
        "blocks/mlp_fc/bias": (L, 4 * E),
        "blocks/mlp_proj/kernel": (L, 4 * E, E),
        "blocks/mlp_proj/bias": (L, E),
        "lnf/scale": (E,), "lnf/bias": (E,),
    }
    assert {leaf.dtype for leaf in jax.tree.leaves(params)} \
        == {jnp.dtype(jnp.float32)}
    # every leaf's rule names no more dimensions than the leaf has
    specs = gpt2_partition_rules().specs(params)
    is_spec = lambda s: isinstance(s, jax.sharding.PartitionSpec)  # noqa: E731
    for leaf, spec in zip(jax.tree.leaves(params),
                          jax.tree.leaves(specs, is_leaf=is_spec)):
        assert len(spec) <= leaf.ndim


def test_another_key_compiles_nothing(cfg):
    init_gpt2.clear_cache()
    init_gpt2(jax.random.PRNGKey(0), cfg)
    assert init_gpt2._cache_size() == 1
    other = init_gpt2(jax.random.PRNGKey(1), cfg)
    assert init_gpt2._cache_size() == 1
    assert not np.array_equal(
        other["wte"], init_gpt2(jax.random.PRNGKey(0), cfg)["wte"])
