"""Llama-family model: RoPE/RMSNorm/SwiGLU/GQA correctness + SPMD.

Second model family (SURVEY.md §2.4 breadth) built TPU-first like
models/gpt2.py.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from ray_tpu.models.llama import (
    LlamaConfig,
    _rope,
    init_llama,
    llama_forward,
    llama_loss,
    llama_partition_rules,
)


@pytest.fixture(scope="module")
def tiny():
    cfg = LlamaConfig.tiny()
    params = init_llama(jax.random.PRNGKey(0), cfg)
    return cfg, params


def test_forward_shape_and_finite(tiny):
    cfg, params = tiny
    toks = jnp.zeros((2, 16), jnp.int32)
    logits = jax.jit(lambda p, t: llama_forward(p, t, cfg))(params, toks)
    assert logits.shape == (2, 16, cfg.padded_vocab)
    assert np.isfinite(np.asarray(logits)).all()


def test_rope_preserves_norm_and_relative_shift():
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 8, 2, 16))
    r = _rope(x, 10000.0)
    np.testing.assert_allclose(
        np.linalg.norm(np.asarray(x), axis=-1),
        np.linalg.norm(np.asarray(r), axis=-1), rtol=1e-5)
    # rotation at position 0 is the identity
    np.testing.assert_allclose(np.asarray(r[:, 0]), np.asarray(x[:, 0]),
                               rtol=1e-5)
    # RoPE is relative: q·k after rotation depends only on the offset
    q = jax.random.normal(jax.random.PRNGKey(2), (1, 8, 1, 16))
    k = jax.random.normal(jax.random.PRNGKey(3), (1, 8, 1, 16))
    # place the same q,k content at different absolute positions
    qa = jnp.roll(q, 2, axis=1)
    ka = jnp.roll(k, 2, axis=1)
    dot1 = jnp.sum(_rope(q, 1e4)[0, 3, 0] * _rope(k, 1e4)[0, 1, 0])
    dot2 = jnp.sum(_rope(qa, 1e4)[0, 5, 0] * _rope(ka, 1e4)[0, 3, 0])
    np.testing.assert_allclose(float(dot1), float(dot2), rtol=1e-4)


def test_gqa_reduces_kv_params(tiny):
    cfg, params = tiny
    E, hd = cfg.n_embd, cfg.head_dim
    assert params["blocks"]["wk"].shape == (cfg.n_layer, E,
                                            cfg.n_kv_head * hd)
    assert params["blocks"]["wq"].shape == (cfg.n_layer, E, E)
    assert cfg.n_kv_head < cfg.n_head


def test_loss_decreases_under_training(tiny):
    cfg, params = tiny
    tx = optax.adam(1e-3)
    opt = tx.init(params)
    toks = jax.random.randint(jax.random.PRNGKey(4), (4, 33), 0,
                              cfg.vocab_size, jnp.int32)
    batch = {"tokens": toks[:, :-1], "targets": toks[:, 1:]}

    @jax.jit
    def step(params, opt):
        loss, g = jax.value_and_grad(
            lambda p: llama_loss(p, batch, cfg))(params)
        up, opt = tx.update(g, opt)
        return optax.apply_updates(params, up), opt, loss

    losses = []
    for _ in range(8):
        params, opt, loss = step(params, opt)
        losses.append(float(loss))
    assert losses[-1] < losses[0] - 0.1
    assert losses[0] == pytest.approx(np.log(cfg.vocab_size), rel=0.2)


def test_spmd_sharded_step_matches_single_device():
    """The sharded train step over an fsdp x tensor mesh computes the
    same loss as single-device execution (SPMD-equivalence)."""
    from ray_tpu.parallel.mesh import MeshSpec, build_mesh
    from ray_tpu.train.spmd import (
        batch_shardings,
        init_sharded_state,
        make_train_step,
    )

    cfg = LlamaConfig.tiny()
    tx = optax.adamw(1e-3)
    toks = jax.random.randint(jax.random.PRNGKey(5), (8, 33), 0,
                              cfg.vocab_size, jnp.int32)
    batch = {"tokens": toks[:, :-1], "targets": toks[:, 1:]}
    losses = {}
    for name, spec in (("single", MeshSpec(data=1)),
                       ("sharded", MeshSpec(data=2, fsdp=2, tensor=2))):
        devices = jax.devices()[:1] if name == "single" else jax.devices()[:8]
        mesh = build_mesh(spec, devices=devices)
        state = init_sharded_state(
            lambda: init_llama(jax.random.PRNGKey(0), cfg), tx, mesh,
            llama_partition_rules())
        b = jax.device_put(batch, batch_shardings(mesh, batch))
        step = make_train_step(lambda p, bb: llama_loss(p, bb, cfg), tx)
        with jax.set_mesh(mesh):
            state, metrics = step(state, b)
        losses[name] = float(metrics["loss"])
    np.testing.assert_allclose(losses["single"], losses["sharded"],
                               rtol=1e-4)


def test_tiny_keeps_its_tree_and_its_logits():
    """The dense presets are what they were before the block was written
    over three helpers (`_qkv`, `_ffn`, `_head`) and the config grew the
    OLMoE fields: the same parameter tree (paths, shapes, dtypes) and,
    in float32 on the CPU, logits within 1e-6 of values recorded from the
    commit before (PR 26's, `llama_forward` on these tokens)."""
    cfg = LlamaConfig.tiny()
    params = init_llama(jax.random.PRNGKey(0), cfg)
    L, E, F, KV = 2, 128, 384, 64
    assert jax.tree.map(lambda a: (a.shape, str(a.dtype)), params) == {
        "wte": ((512, E), "float32"), "lnf": ((E,), "float32"),
        "blocks": {
            "ln_attn": ((L, E), "float32"), "ln_mlp": ((L, E), "float32"),
            "wq": ((L, E, E), "float32"), "wk": ((L, E, KV), "float32"),
            "wv": ((L, E, KV), "float32"), "wo": ((L, E, E), "float32"),
            "w_gate": ((L, E, F), "float32"), "w_up": ((L, E, F), "float32"),
            "w_down": ((L, F, E), "float32")}}
    toks = (jnp.arange(24, dtype=jnp.int32).reshape(2, 12) * 7 + 3) \
        % cfg.vocab_size
    logits = np.asarray(llama_forward(params, toks, cfg))
    np.testing.assert_allclose(
        logits[1, -1, :6],
        [0.12285878, 0.028353298, -0.050259236, -0.23707722, 0.023302875,
         -0.5333573], atol=1e-6, rtol=0)
    np.testing.assert_allclose(
        logits[0, 3, 100:104],
        [-0.1831285, -0.17130451, 0.24605943, 0.073974], atol=1e-6, rtol=0)
    assert abs(float(np.abs(logits).mean()) - 0.18084274) < 1e-6
