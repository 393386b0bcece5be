"""ZeRO-1 sharded weight update (train/spmd.py zero_stage=1).

Gates the three tentpole claims:
- loss parity with the unsharded step (atol 1e-5, several steps) on
  gpt2 and llama — sharding is layout, not arithmetic. The strict gate
  uses an elementwise-stable optimizer (sgd+momentum: param-shaped
  state, no ulp amplification, parity is exact); the adamw case
  documents the mu/sqrt(nu) amplification of cross-program
  reduction-order noise and gates the first steps plus the byte win.
- per-chip optimizer bytes shrink ~1/data-axis-size.
- the compiled program is structurally restructured: the ZeRO-1 step
  carries the extra resharding collectives (XLA:CPU realizes the
  scatter as allreduce + slice and the param regather as all-gathers;
  TPU forms true reduce-scatter) — plus the waterfall's split-phase
  and census plumbing (the PR's collective-attribution satellite).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import optax

from ray_tpu.models.gpt2 import (
    GPT2Config,
    gpt2_loss,
    gpt2_partition_rules,
    init_gpt2,
)
from ray_tpu.parallel.mesh import MeshSpec, build_mesh
from ray_tpu.train import spmd
from ray_tpu.train.spmd import (
    batch_shardings,
    init_sharded_state,
    make_train_step,
    optimizer_state_bytes,
)

DATA = 4  # data-axis size the byte-shrink assertions divide by


@pytest.fixture(scope="module")
def mesh():
    return build_mesh(MeshSpec(data=DATA, tensor=2))


def _batch(mesh, vocab, B=8, T=64, seed=0):
    toks = np.random.RandomState(seed).randint(
        0, vocab, (B, T + 1)).astype(np.int32)
    b = {"tokens": jnp.asarray(toks[:, :-1]),
         "targets": jnp.asarray(toks[:, 1:])}
    return jax.device_put(b, batch_shardings(mesh, b))


def _run(mesh, rules, init_fn, loss_fn, tx, batch, shard, steps):
    state = init_sharded_state(init_fn, tx, mesh, rules,
                               zero_stage=1 if shard else 0)
    step = make_train_step(loss_fn, tx, zero_stage=1 if shard else 0,
                           mesh=mesh if shard else None,
                           rules=rules if shard else None)
    losses = []
    with jax.set_mesh(mesh):
        for _ in range(steps):
            state, m = step(state, batch)
            losses.append(float(m["loss"]))
    return state, losses


def test_gpt2_loss_parity_sharded_vs_replicated(mesh):
    cfg = GPT2Config.tiny()
    rules = gpt2_partition_rules()
    tx = optax.sgd(0.05, momentum=0.9)
    batch = _batch(mesh, cfg.vocab_size)

    def init_fn():
        return init_gpt2(jax.random.PRNGKey(0), cfg)

    def loss_fn(p, b):
        return gpt2_loss(p, b, cfg)

    s_r, l_r = _run(mesh, rules, init_fn, loss_fn, tx, batch, False, 5)
    s_z, l_z = _run(mesh, rules, init_fn, loss_fn, tx, batch, True, 5)
    assert l_r[0] > l_r[-1]  # it actually trains
    np.testing.assert_allclose(l_r, l_z, atol=1e-5)
    # params track too — same update arithmetic, different layout
    for a, b in zip(jax.tree.leaves(s_r.params),
                    jax.tree.leaves(s_z.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-5)


def test_llama_loss_parity_sharded_vs_replicated(mesh):
    from ray_tpu.models.llama import (
        LlamaConfig,
        init_llama,
        llama_loss,
        llama_partition_rules,
    )

    cfg = LlamaConfig.tiny()
    rules = llama_partition_rules()
    tx = optax.sgd(0.05, momentum=0.9)
    batch = _batch(mesh, cfg.vocab_size, T=32, seed=1)

    def init_fn():
        return init_llama(jax.random.PRNGKey(0), cfg)

    def loss_fn(p, b):
        return llama_loss(p, b, cfg)

    _, l_r = _run(mesh, rules, init_fn, loss_fn, tx, batch, False, 5)
    _, l_z = _run(mesh, rules, init_fn, loss_fn, tx, batch, True, 5)
    np.testing.assert_allclose(l_r, l_z, atol=1e-5)


def test_optimizer_bytes_shrink_one_over_data_axis(mesh):
    """The memory claim itself: per-chip optimizer bytes under ZeRO-1
    ~1/DATA of replicated (small slack for the scalar/indivisible
    leaves that stay replicated), and the gauge shows both layouts."""
    cfg = GPT2Config.tiny()
    rules = gpt2_partition_rules()
    tx = optax.adamw(3e-4)  # two param-shaped moments — the real shape

    def init_fn():
        return init_gpt2(jax.random.PRNGKey(0), cfg)

    s_r = init_sharded_state(init_fn, tx, mesh, rules)
    s_z = init_sharded_state(init_fn, tx, mesh, rules,
                             zero_stage=1)
    b_r = optimizer_state_bytes(s_r.opt_state)
    b_z = optimizer_state_bytes(s_z.opt_state)
    assert b_r > 0
    ratio = b_z / b_r
    assert ratio <= 1.0 / DATA * 1.25, (b_r, b_z, ratio)
    assert ratio >= 1.0 / DATA * 0.75, (b_r, b_z, ratio)
    from ray_tpu.train.spmd import _optimizer_bytes_gauge

    exposed = "\n".join(_optimizer_bytes_gauge().expose())
    assert 'layout="replicated"' in exposed
    assert 'layout="zero1"' in exposed


def test_adamw_sharded_update_tracks_and_shrinks(mesh):
    """adamw: first-step loss identical, later steps track loosely
    (mu/sqrt(nu) amplifies cross-program reduction-order ulps — see
    TRAINING.md), and the byte win still holds end-to-end."""
    cfg = GPT2Config.tiny()
    rules = gpt2_partition_rules()
    tx = optax.adamw(1e-3)
    batch = _batch(mesh, cfg.vocab_size, seed=2)

    def init_fn():
        return init_gpt2(jax.random.PRNGKey(0), cfg)

    def loss_fn(p, b):
        return gpt2_loss(p, b, cfg)

    s_r, l_r = _run(mesh, rules, init_fn, loss_fn, tx, batch, False, 4)
    s_z, l_z = _run(mesh, rules, init_fn, loss_fn, tx, batch, True, 4)
    assert abs(l_r[0] - l_z[0]) <= 1e-5  # same params -> same loss
    np.testing.assert_allclose(l_r, l_z, atol=5e-3)
    assert l_z[0] > l_z[-1]
    assert optimizer_state_bytes(s_z.opt_state) \
        < 0.5 * optimizer_state_bytes(s_r.opt_state)


def test_zero1_program_restructures_collectives(mesh):
    """Structural census: the ZeRO-1 program carries the resharding
    collectives the replicated step doesn't (param all-gathers; true
    reduce-scatter where the backend forms it)."""
    from ray_tpu.parallel.ops import collective_op_counts

    cfg = GPT2Config.tiny()
    rules = gpt2_partition_rules()
    tx = optax.sgd(0.05, momentum=0.9)
    batch = _batch(mesh, cfg.vocab_size)

    def loss_fn(p, b):
        return gpt2_loss(p, b, cfg)

    def census(shard):
        state = init_sharded_state(
            lambda: init_gpt2(jax.random.PRNGKey(0), cfg), tx, mesh,
            rules, zero_stage=1 if shard else 0)
        step = make_train_step(loss_fn, tx, zero_stage=1 if shard else 0,
                               mesh=mesh if shard else None,
                               rules=rules if shard else None,
                               donate=False)
        with jax.set_mesh(mesh):
            txt = step.jitted.lower(state, batch).compile().as_text()
        return collective_op_counts(txt)

    plain, zero1 = census(False), census(True)
    assert plain.get("allreduce", 0) > 0  # DP grad reduction exists
    assert (zero1.get("reduce_scatter", 0) > 0
            or zero1.get("all_gather", 0) > plain.get("all_gather", 0)), \
        (plain, zero1)


def test_waterfall_splits_collective_phase_and_censuses():
    """The attribution satellite, mechanically: (a) collective_seconds
    carries the canonical op labels and sums_by_tag groups them; (b) an
    attributed ZeRO-1 step records the program collective census and
    the table prints it; (c) split collective.<op> phases render."""
    from ray_tpu.util.collective import _OP_LABELS, _collective_seconds

    # (a) canonical labels: the host path maps its round kinds
    assert _OP_LABELS["allgather"] == "all_gather"
    assert _OP_LABELS["reducescatter"] == "reduce_scatter"
    h = _collective_seconds()
    base = h.sums_by_tag("op")
    h.observe(0.25, tags={"op": "all_gather"})
    h.observe(0.5, tags={"op": "reduce_scatter"})
    now = h.sums_by_tag("op")
    assert now.get("all_gather", 0) - base.get("all_gather", 0) \
        == pytest.approx(0.25)
    assert now.get("reduce_scatter", 0) - base.get("reduce_scatter", 0) \
        == pytest.approx(0.5)

    # (b) attributed zero1 step -> census lands in the waterfall
    cfg = GPT2Config.tiny()
    mesh = build_mesh(MeshSpec(data=4, tensor=2))
    rules = gpt2_partition_rules()
    tx = optax.sgd(0.05, momentum=0.9)
    batch = _batch(mesh, cfg.vocab_size)
    state = init_sharded_state(
        lambda: init_gpt2(jax.random.PRNGKey(0), cfg), tx, mesh, rules,
        zero_stage=1)
    step = make_train_step(lambda p, b: gpt2_loss(p, b, cfg), tx,
                           zero_stage=1, mesh=mesh, rules=rules)
    spmd.waterfall.reset()
    spmd.enable_step_waterfall(True)
    try:
        with jax.set_mesh(mesh):
            state, m = step(state, batch)
            state, m = step(state, batch)
    finally:
        spmd.enable_step_waterfall(False)
    s = spmd.waterfall.summary()
    census = s.get("program_collectives", {})
    assert census, s
    assert census.get("all_gather", 0) > 0 or \
        census.get("reduce_scatter", 0) > 0, census
    assert "in-program collectives" in spmd.waterfall.table()
    # census survives the reset a timed bench window performs
    spmd.waterfall.reset()
    assert spmd.waterfall.summary().get("program_collectives") == census

    # (c) split phases render through add/summary/table
    spmd.waterfall.reset()
    spmd.waterfall.add({"compute": 0.8, "collective.reduce_scatter": 0.15,
                        "collective.all_gather": 0.05})
    out = spmd.waterfall.summary()
    assert out["phases"]["collective.reduce_scatter"] == \
        pytest.approx(0.15)
    table = spmd.waterfall.table()
    assert "collective.reduce_scatter" in table
    assert "collective.all_gather" in table
    spmd.waterfall.reset()
