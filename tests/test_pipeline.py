"""Pipeline parallelism vs sequential stage application."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from ray_tpu.parallel import ops
from ray_tpu.parallel.mesh import MeshSpec, build_mesh
from ray_tpu.parallel.pipeline import pipeline_apply


@pytest.fixture(scope="module")
def pipe_mesh():
    return build_mesh(MeshSpec(data=2, pipe=4, tensor=1))


def _stage_fn(p, h):
    return jnp.tanh(h @ p["w"] + p["b"])


def _stacked_params(key, S, d):
    ks = jax.random.split(key, S)
    return {
        "w": jnp.stack([jax.random.normal(k, (d, d)) * 0.5 for k in ks]),
        "b": jnp.zeros((S, d)),
    }


def _sequential(params, x, S):
    h = x
    for i in range(S):
        h = _stage_fn(jax.tree.map(lambda a: a[i], params), h)
    return h


def test_pipeline_matches_sequential(pipe_mesh):
    S, d, B = 4, 8, 16
    params = _stacked_params(jax.random.PRNGKey(0), S, d)
    x = jax.random.normal(jax.random.PRNGKey(1), (B, d))

    piped = ops.shard_map(
        lambda p, xx: pipeline_apply(
            lambda q, h: _stage_fn(jax.tree.map(lambda a: a[0], q), h),
            p, xx, "pipe"),
        pipe_mesh,
        in_specs=(P("pipe"), P()),
        out_specs=P())
    out = piped(params, x)
    ref = _sequential(params, x, S)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


def test_pipeline_more_microbatches(pipe_mesh):
    S, d, B = 4, 8, 32
    params = _stacked_params(jax.random.PRNGKey(2), S, d)
    x = jax.random.normal(jax.random.PRNGKey(3), (B, d))
    piped = ops.shard_map(
        lambda p, xx: pipeline_apply(
            lambda q, h: _stage_fn(jax.tree.map(lambda a: a[0], q), h),
            p, xx, "pipe", num_microbatches=8),
        pipe_mesh, in_specs=(P("pipe"), P()), out_specs=P())
    np.testing.assert_allclose(np.asarray(piped(params, x)),
                               np.asarray(_sequential(params, x, S)),
                               atol=1e-5, rtol=1e-5)


def test_pipeline_differentiable(pipe_mesh):
    S, d, B = 4, 4, 8
    params = _stacked_params(jax.random.PRNGKey(4), S, d)
    x = jax.random.normal(jax.random.PRNGKey(5), (B, d))

    piped = ops.shard_map(
        lambda p, xx: pipeline_apply(
            lambda q, h: _stage_fn(jax.tree.map(lambda a: a[0], q), h),
            p, xx, "pipe"),
        pipe_mesh, in_specs=(P("pipe"), P()), out_specs=P())

    g1 = jax.grad(lambda p: jnp.sum(piped(p, x) ** 2))(params)
    g2 = jax.grad(lambda p: jnp.sum(_sequential(p, x, S) ** 2))(params)
    for a, b in zip(jax.tree_util.tree_leaves(g1),
                    jax.tree_util.tree_leaves(g2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-4, rtol=1e-3)


# ------------------------------------------- interleaved (1F1B-class)


def test_interleaved_matches_sequential(pipe_mesh):
    """Circular schedule with R virtual stages per device == applying
    all S*R stages in order (round-robin placement reorder)."""
    from ray_tpu.parallel.pipeline import pipeline_apply_interleaved

    S, R, d, B = 4, 2, 8, 16
    V = S * R
    params = _stacked_params(jax.random.PRNGKey(3), V, d)
    x = jax.random.normal(jax.random.PRNGKey(4), (B, d))
    ref = _sequential(params, x, V)
    order = np.argsort(np.arange(V) % S, kind="stable")
    rr = jax.tree.map(lambda a: a[order], params)
    out = jax.jit(ops.shard_map(
        lambda p, xx: pipeline_apply_interleaved(
            _stage_fn, p, xx, "pipe", num_microbatches=8, num_repeats=R),
        pipe_mesh, in_specs=(P("pipe"), P()), out_specs=P()))(rr, x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=1e-6)


def test_interleaved_differentiable(pipe_mesh):
    from ray_tpu.parallel.pipeline import pipeline_apply_interleaved

    S, R, d, B = 4, 2, 8, 8
    V = S * R
    params = _stacked_params(jax.random.PRNGKey(5), V, d)
    order = np.argsort(np.arange(V) % S, kind="stable")
    rr = jax.tree.map(lambda a: a[order], params)
    x = jax.random.normal(jax.random.PRNGKey(6), (B, d))

    def loss(p):
        out = ops.shard_map(
            lambda pp, xx: pipeline_apply_interleaved(
                _stage_fn, pp, xx, "pipe", num_microbatches=4,
                num_repeats=R),
            pipe_mesh, in_specs=(P("pipe"), P()), out_specs=P())(p, x)
        return jnp.mean(out ** 2)

    g = jax.jit(jax.grad(loss))(rr)
    flat = jax.tree.leaves(jax.tree.map(np.asarray, g))
    assert all(np.isfinite(a).all() for a in flat)
    assert any(np.abs(a).sum() > 0 for a in flat)


def test_pipelined_transformer_hybrid_mesh():
    """Multi-stage transformer (ring attention over fsdp inside the
    blocks, interleaved pipeline over pipe, tensor/dcn left to GSPMD):
    two SGD steps reduce the loss on an 8-device hybrid mesh."""
    from jax.sharding import NamedSharding

    from ray_tpu.models.pipelined import (
        PipelinedConfig,
        init_pipelined,
        pipelined_shardings,
        pipelined_train_step,
    )

    mesh = build_mesh(MeshSpec(dcn=2, pipe=2, fsdp=2, tensor=1))
    cfg = PipelinedConfig()
    params = init_pipelined(jax.random.PRNGKey(0), cfg)
    params = jax.device_put(params, pipelined_shardings(params, cfg, mesh))
    rng = np.random.RandomState(0)
    toks = rng.randint(0, cfg.vocab_size,
                       (8, cfg.block_size + 1)).astype(np.int32)
    batch = jax.device_put(
        {"tokens": jnp.asarray(toks[:, :-1]),
         "targets": jnp.asarray(toks[:, 1:])},
        NamedSharding(mesh, P(("dcn", "data"),)))
    step = pipelined_train_step(cfg, mesh)
    with jax.set_mesh(mesh):
        p1, l1 = step(params, batch)
        _, l2 = step(p1, batch)
    assert float(l2) < float(l1)
