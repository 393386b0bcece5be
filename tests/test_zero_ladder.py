"""ZeRO-2/3 ladder (train/spmd.py zero_stage + accum_steps).

Extends the ZeRO-1 gates of test_zero1.py up the ladder:
- stage 2 keeps the grad-accum buffer resident reduce-scattered 1/N
  between accumulation boundaries; stage 3 shards the resident params
  1/N with a just-in-time all-gather inside the jitted step.
- Parity is exact arithmetic, not "close": the double-constraint pin
  (grads to the rule layout before the scatter; stage-3 params to the
  rule layout before the loss) keeps every GEMM partitioning identical
  to the unsharded program, so sgd(+momentum) losses AND params match
  at 1e-5 on gpt2 and llama.
- The memory rungs are test-gated at <= 1.25/N per component, and the
  stage-3 program structurally carries the param gathers.
"""

import numpy as np
import pytest

import jax
import optax

from ray_tpu.models.gpt2 import (
    GPT2Config,
    gpt2_loss,
    gpt2_partition_rules,
    init_gpt2,
)
from ray_tpu.parallel.mesh import MeshSpec, build_mesh
from ray_tpu.train.spmd import (
    init_sharded_state,
    make_train_step,
    optimizer_state_bytes,
)

from tests.test_zero1 import _batch

DATA = 4  # data-axis size the byte-shrink assertions divide by


@pytest.fixture(scope="module")
def mesh():
    return build_mesh(MeshSpec(data=DATA, tensor=2))


def _run(mesh, rules, init_fn, loss_fn, tx, batch, stage, steps,
         accum=1):
    state = init_sharded_state(init_fn, tx, mesh, rules,
                               zero_stage=stage, accum_steps=accum)
    step = make_train_step(loss_fn, tx, zero_stage=stage,
                           mesh=mesh if stage else None,
                           rules=rules if stage else None,
                           accum_steps=accum)
    losses = []
    with jax.set_mesh(mesh):
        for _ in range(steps):
            state, m = step(state, batch)
            losses.append(float(m["loss"]))
    return state, losses


def _gpt2_parts(mesh, seed=0):
    cfg = GPT2Config.tiny()
    rules = gpt2_partition_rules()
    batch = _batch(mesh, cfg.vocab_size, seed=seed)

    def init_fn():
        return init_gpt2(jax.random.PRNGKey(0), cfg)

    def loss_fn(p, b):
        return gpt2_loss(p, b, cfg)

    return rules, init_fn, loss_fn, batch


@pytest.fixture(scope="module")
def gpt2_reference(mesh):
    """The stage-0 oracle run, shared by both parity rungs (one
    compile instead of one per parametrization)."""
    rules, init_fn, loss_fn, batch = _gpt2_parts(mesh)
    tx = optax.sgd(0.05, momentum=0.9)
    state, losses = _run(mesh, rules, init_fn, loss_fn, tx, batch,
                         0, 4)
    return state, losses, batch


@pytest.mark.parametrize("stage", [2, 3])
def test_gpt2_parity_up_the_ladder(mesh, gpt2_reference, stage):
    """Loss AND param parity at 1e-5 vs the unsharded step, stages 2
    and 3, sgd+momentum (elementwise-stable update, exact gate)."""
    s_r, l_r, batch = gpt2_reference
    rules, init_fn, loss_fn, _ = _gpt2_parts(mesh)
    tx = optax.sgd(0.05, momentum=0.9)
    s_z, l_z = _run(mesh, rules, init_fn, loss_fn, tx, batch, stage, 4)
    assert l_r[0] > l_r[-1]  # it actually trains
    np.testing.assert_allclose(l_r, l_z, atol=1e-5)
    for a, b in zip(jax.tree.leaves(s_r.params),
                    jax.tree.leaves(s_z.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-5)


def _llama_parts(mesh):
    from ray_tpu.models.llama import (
        LlamaConfig,
        init_llama,
        llama_loss,
        llama_partition_rules,
    )

    cfg = LlamaConfig.tiny()
    rules = llama_partition_rules()
    batch = _batch(mesh, cfg.vocab_size, T=32, seed=1)

    def init_fn():
        return init_llama(jax.random.PRNGKey(0), cfg)

    def loss_fn(p, b):
        return llama_loss(p, b, cfg)

    return rules, init_fn, loss_fn, batch


@pytest.fixture(scope="module")
def llama_reference(mesh):
    rules, init_fn, loss_fn, batch = _llama_parts(mesh)
    tx = optax.sgd(0.05, momentum=0.9)
    _, losses = _run(mesh, rules, init_fn, loss_fn, tx, batch, 0, 4)
    return losses, batch


@pytest.mark.parametrize("stage", [2, 3])
def test_llama_parity_up_the_ladder(mesh, llama_reference, stage):
    l_r, batch = llama_reference
    rules, init_fn, loss_fn, _ = _llama_parts(mesh)
    tx = optax.sgd(0.05, momentum=0.9)
    _, l_z = _run(mesh, rules, init_fn, loss_fn, tx, batch, stage, 4)
    np.testing.assert_allclose(l_r, l_z, atol=1e-5)


def test_grad_accum_parity_across_stages(mesh):
    """accum_steps=2: the accumulate-then-select update must match the
    accum_steps=2 unsharded step exactly at stages 2 and 3 (losses at
    every microstep — the select keeps params frozen off-boundary)."""
    rules, init_fn, loss_fn, batch = _gpt2_parts(mesh, seed=3)
    tx = optax.sgd(0.05, momentum=0.9)
    s0, l0 = _run(mesh, rules, init_fn, loss_fn, tx, batch, 0, 6,
                  accum=2)
    # off-boundary steps keep params frozen -> pairwise-equal losses
    assert l0[0] == pytest.approx(l0[1], abs=1e-6)
    assert l0[0] > l0[-1]
    for stage in (2, 3):
        s_z, l_z = _run(mesh, rules, init_fn, loss_fn, tx, batch,
                        stage, 6, accum=2)
        np.testing.assert_allclose(l0, l_z, atol=1e-5)
        for a, b in zip(jax.tree.leaves(s0.params),
                        jax.tree.leaves(s_z.params)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-5)


def test_state_bytes_shrink_per_rung(mesh):
    """The per-component memory claims: grad-accum bytes 1/N at stage
    >= 2, resident param bytes 1/N at stage 3 (<= 1.25/N slack for
    indivisible leaves), optimizer bytes 1/N from stage 1 on — and the
    per-component gauges expose both layouts."""
    rules, init_fn, _, _ = _gpt2_parts(mesh)
    tx = optax.sgd(0.05, momentum=0.9)

    def bytes_at(stage):
        s = init_sharded_state(init_fn, tx, mesh, rules,
                               zero_stage=stage, accum_steps=2)
        return (optimizer_state_bytes(s.opt_state),
                optimizer_state_bytes(s.grad_accum),
                optimizer_state_bytes(s.params))

    o0, g0, p0 = bytes_at(0)
    o2, g2, p2 = bytes_at(2)
    o3, g3, p3 = bytes_at(3)
    assert g0 > 0 and p0 > 0
    bound = 1.25 / DATA
    assert o2 / o0 <= bound, (o0, o2)          # stage >= 1 rung
    assert g2 / g0 <= bound, (g0, g2)          # stage >= 2 rung
    assert p2 == p0                            # params untouched < 3
    assert g3 / g0 <= bound and o3 / o0 <= bound
    assert p3 / p0 <= bound, (p0, p3)          # stage 3 rung

    from ray_tpu.train.spmd import (
        _grad_state_bytes_gauge,
        _param_state_bytes_gauge,
    )

    exposed_g = "\n".join(_grad_state_bytes_gauge().expose())
    assert 'layout="replicated"' in exposed_g
    assert 'layout="zero2"' in exposed_g
    exposed_p = "\n".join(_param_state_bytes_gauge().expose())
    assert 'layout="replicated"' in exposed_p
    assert 'layout="zero3"' in exposed_p


def test_zero3_program_carries_param_gathers(mesh):
    """Structural census: the stage-3 program all-gathers the resident
    1/N params just-in-time inside the step — collectives the
    replicated program does not have."""
    from ray_tpu.parallel.ops import collective_op_counts

    rules, init_fn, loss_fn, batch = _gpt2_parts(mesh)
    tx = optax.sgd(0.05, momentum=0.9)

    def census(stage):
        state = init_sharded_state(init_fn, tx, mesh, rules,
                                   zero_stage=stage)
        step = make_train_step(loss_fn, tx, zero_stage=stage,
                               mesh=mesh if stage else None,
                               rules=rules if stage else None,
                               donate=False)
        with jax.set_mesh(mesh):
            txt = step.jitted.lower(state, batch).compile().as_text()
        return collective_op_counts(txt)

    plain, zero3 = census(0), census(3)
    assert plain.get("allreduce", 0) > 0  # DP grad reduction exists
    assert zero3.get("all_gather", 0) > plain.get("all_gather", 0), \
        (plain, zero3)


def test_resolve_zero_stage_back_compat():
    """No stage named is stage 0; out-of-range stages are rejected."""
    from ray_tpu.train.spmd import _resolve_zero_stage

    assert _resolve_zero_stage(None) == 0
    assert _resolve_zero_stage(2) == 2
    assert _resolve_zero_stage(3) == 3
    with pytest.raises(ValueError):
        _resolve_zero_stage(4)


def test_zero_shardings_component_rungs(mesh):
    """zero_shardings applies the +data-axis layout iff the stage
    reaches the component's rung (optimizer: 1, grads: 2, params: 3),
    else falls back to the rule layout."""
    from ray_tpu.parallel.sharding import PartitionRules
    from ray_tpu.train.spmd import zero1_shardings, zero_shardings

    rules = PartitionRules([])
    tree = {"w": np.zeros((8, 8), np.float32)}
    zero = zero1_shardings(rules, tree, mesh)["w"]
    for component, rung in (("optimizer", 1), ("grads", 2),
                            ("params", 3)):
        for stage in range(4):
            got = zero_shardings(rules, tree, mesh, stage,
                                 component=component)["w"]
            want = zero if stage >= rung else \
                rules.shardings(tree, mesh)["w"]
            assert got.spec == want.spec, (component, stage, got)
    with pytest.raises(ValueError):
        zero_shardings(rules, tree, mesh, 1, component="nonsense")


def test_gather_share_gauge_populates_at_stage3(mesh):
    """Attribution runs at zero_stage>=3 set train_zero_gather_share —
    the watchtower train-zero-gather-stall rule's input."""
    from ray_tpu.train import spmd
    from ray_tpu.util.metrics import Gauge

    rules, init_fn, loss_fn, batch = _gpt2_parts(mesh, seed=5)
    tx = optax.sgd(0.05, momentum=0.9)
    state = init_sharded_state(init_fn, tx, mesh, rules, zero_stage=3)
    step = make_train_step(loss_fn, tx, zero_stage=3, mesh=mesh,
                           rules=rules)
    spmd.waterfall.reset()
    spmd.enable_step_waterfall(True)
    try:
        with jax.set_mesh(mesh):
            state, _ = step(state, batch)
            state, _ = step(state, batch)
    finally:
        spmd.enable_step_waterfall(False)
    g = Gauge("train_zero_gather_share", "")  # registry-backed handle
    share = g._values.get((), None)
    assert share is not None, "gauge never set"
    assert 0.0 <= share <= 1.0, share


def _plain_step(loss_fn, tx, mesh, rules, stage, accum):
    """`make_train_step`'s program written out with NO fence between
    the backward pass and the gradients' consumers: what the fenced
    step is held to."""
    import jax.numpy as jnp

    from ray_tpu.train.spmd import TrainState, zero1_shardings

    def pin(tree):
        return jax.tree.map(jax.lax.with_sharding_constraint, tree,
                            rules.shardings(tree, mesh))

    def scatter(tree):
        return jax.tree.map(jax.lax.with_sharding_constraint, tree,
                            zero1_shardings(rules, tree, mesh))

    def step(state, batch):
        params = pin(state.params) if stage >= 3 else state.params
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        gnorm = optax.global_norm(grads)
        held = state.params
        if stage >= 1:
            grads = scatter(pin(grads))
            held = held if stage >= 3 else scatter(held)
        if accum > 1:
            acc = jax.tree.map(jnp.add, state.grad_accum, grads)
            boundary = (state.step + 1) % accum == 0

            def at_boundary(new, old):
                return jax.tree.map(
                    lambda a, b: jnp.where(boundary, a, b), new, old)

            updates, opt = tx.update(
                jax.tree.map(lambda a: a / accum, acc), state.opt_state,
                held)
            new = at_boundary(optax.apply_updates(held, updates), held)
            opt = at_boundary(opt, state.opt_state)
            acc = at_boundary(jax.tree.map(jnp.zeros_like, acc), acc)
        else:
            updates, opt = tx.update(grads, state.opt_state, held)
            new, acc = optax.apply_updates(held, updates), state.grad_accum
        if stage in (1, 2):
            new = pin(new)
        elif stage >= 3:
            new = scatter(new)
        return (TrainState(params=new, opt_state=opt, step=state.step + 1,
                           grad_accum=acc),
                {"loss": loss, "grad_norm": gnorm})

    return jax.jit(step)


@pytest.mark.parametrize("stage,accum", [(0, 1), (0, 2), (1, 1), (2, 1),
                                         (2, 2), (3, 1)])
def test_gradient_fence_is_the_identity_on_values(mesh, stage, accum):
    """`make_train_step` fences the gradients from whatever consumes
    them (one `optimization_barrier` after `value_and_grad`, which keeps
    XLA:TPU from hanging the embedding's update behind the head's
    backward product; TRAINING.md "What a step compiles to"). The fence
    moves no value: three adamw steps give the loss, the gradient norm,
    the params and the optimizer state of the same step written out
    without it, on every rung of the ladder."""
    rules, init_fn, loss_fn, batch = _gpt2_parts(mesh, seed=7)
    tx = optax.adamw(3e-3, weight_decay=0.1)

    def three_steps(step):
        state = init_sharded_state(init_fn, tx, mesh, rules,
                                   zero_stage=stage, accum_steps=accum)
        seen = []
        with jax.set_mesh(mesh):
            for _ in range(3):
                state, m = step(state, batch)
                seen.append((float(m["loss"]), float(m["grad_norm"])))
        return state, seen

    fenced = make_train_step(loss_fn, tx, zero_stage=stage,
                             mesh=mesh if stage else None,
                             rules=rules if stage else None,
                             accum_steps=accum, donate=False)
    s_f, m_f = three_steps(fenced)
    plain = _plain_step(loss_fn, tx, mesh, rules, stage, accum)
    s_p, m_p = three_steps(plain)
    np.testing.assert_allclose(m_f, m_p, rtol=0, atol=1e-5)
    for a, b in zip(jax.tree.leaves(s_f), jax.tree.leaves(s_p),
                    strict=True):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=0, atol=1e-5)

    # one fence over the whole tree, beside the one `jax.checkpoint`
    # puts in a rematerialised block's backward pass
    with jax.set_mesh(mesh):
        fences = [f.lower(s_f, batch).as_text().count(
            "optimization_barrier") for f in (fenced.jitted, plain)]
    assert fences[0] == fences[1] + 1, fences
