"""MoE layer + expert parallelism tests."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from ray_tpu.models.moe import MoEConfig, init_moe, moe_layer
from ray_tpu.parallel.mesh import MeshSpec, build_mesh


@pytest.fixture(scope="module")
def expert_mesh():
    return build_mesh(MeshSpec(data=2, expert=4, tensor=1))


def _cfg(**kw):
    base = dict(num_experts=4, top_k=2, d_model=32, d_ff=64,
                dtype=jnp.float32)
    base.update(kw)
    return MoEConfig(**base)


def test_moe_forward_shapes_and_aux():
    cfg = _cfg()
    params = init_moe(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, cfg.d_model))
    out, aux = moe_layer(params, x, cfg)
    assert out.shape == x.shape
    assert float(aux) > 0.0  # balanced loss is ~1.0, must be finite


def test_moe_matches_dense_single_expert():
    """With one expert and top_k=1, MoE reduces to a plain MLP."""
    cfg = _cfg(num_experts=1, top_k=1)
    params = init_moe(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 8, cfg.d_model))
    out, _ = moe_layer(params, x, cfg)
    h = jax.nn.gelu(x.reshape(-1, cfg.d_model) @ params["wi"][0])
    ref = (h @ params["wo"][0]).reshape(x.shape)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


def test_moe_differentiable():
    cfg = _cfg()
    params = init_moe(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, cfg.d_model))

    def loss(p):
        out, aux = moe_layer(p, x, cfg)
        return jnp.mean(out ** 2) + 0.01 * aux

    g = jax.grad(loss)(params)
    norms = [float(jnp.linalg.norm(leaf)) for leaf in
             jax.tree_util.tree_leaves(g)]
    assert all(n == n for n in norms)  # no NaNs
    assert any(n > 0 for n in norms)


def test_moe_sharded_over_expert_axis(expert_mesh):
    """Same numbers under jit with the experts' weights sharded over the
    `expert` axis and the tokens over `data`."""
    cfg = _cfg(num_experts=8)
    params = init_moe(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, cfg.d_model))

    ref_out, ref_aux = moe_layer(params, x, cfg)

    with expert_mesh:
        sharded_params = {
            "gate": {"kernel": jax.device_put(
                params["gate"]["kernel"],
                NamedSharding(expert_mesh, P()))},
            "wi": jax.device_put(params["wi"],
                                 NamedSharding(expert_mesh, P("expert"))),
            "wo": jax.device_put(params["wo"],
                                 NamedSharding(expert_mesh, P("expert"))),
        }
        xs = jax.device_put(x, NamedSharding(expert_mesh, P("data")))
        out, aux = jax.jit(
            lambda p, xx: moe_layer(p, xx, cfg))(sharded_params, xs)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref_out),
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(float(aux), float(ref_aux), rtol=1e-5)
