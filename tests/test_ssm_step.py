"""The one-step recurrence's Pallas kernel (`ops/ssm_step.py`) under the
interpreter against the jnp form it replaces on the chip
(`ssm_step.ssm_step_reference`), at both families' shapes cut small: one
group whose heads all share B and C (granite_hybrid) and four groups of
two heads (nemotron_h's eight of eight), one block and many, slots with
and without an owner; then `mamba2.step` whole on both paths, and the predicate that
picks between them."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import mamba2
from ray_tpu.ops import ssm_step
from ray_tpu.serve.llm.cache import StateLayout, StateView

LAYERS, LAYER, SLOTS = 3, 1, 4
# heads, groups, head_dim, state
FAMILIES = {"one-group": (4, 1, 8, 128), "four-groups": (8, 4, 8, 128)}
# one block a layer, and blocks of one slot and two heads (8 KB of state)
BLOCK_BYTES = {"one-block": ssm_step.BLOCK_BYTES, "many-blocks": 8192}
# the lanes' slots: -1 a padded lane; slot 2 (and in the first case 0) has
# no owner
LANES = {"slot-0-unowned": [3, -1, 1], "slot-0-owned": [0, 3, -1, 1]}


def _sizes(family, dtype=jnp.float32):
    H, G, P, N = FAMILIES[family]
    return mamba2.Mamba2Sizes(heads=H, head_dim=P, state=N, groups=G,
                              conv_kernel=4, chunk=16, eps=1e-5, dtype=dtype)


def _view(s, lanes, seed=0):
    layout = StateLayout(LAYERS, SLOTS, s.state_parts())
    keys = jax.random.split(jax.random.PRNGKey(seed), len(layout.parts))
    buffers = {part[0]: jax.random.normal(k, layout.shape(part)).astype(
        part[2]) for k, part in zip(keys, layout.parts)}
    return StateView(layout, buffers, jnp.asarray(lanes, jnp.int32))


def _operands(s, view, seed=1):
    """decay, xdt, B and C in slot order as `mamba2.step` makes them from
    the lanes' rows: a slot no lane owns gets dt = 0 and x = 0."""
    lanes = view.slots.shape[0]
    k = jax.random.split(jax.random.PRNGKey(seed), 5)
    dt = view.to_slots(jax.nn.softplus(jax.random.normal(k[0],
                                                         (lanes, s.heads))))
    A = -jnp.exp(jax.random.normal(k[1], (s.heads,)))
    x = view.to_slots(jax.random.normal(k[2], (lanes, s.heads, s.head_dim)))
    B, C = (view.to_slots(jax.random.normal(key, (lanes, s.groups, s.state)))
            for key in k[3:])
    return jnp.exp(dt * A), x * dt[..., None], B, C


@pytest.mark.parametrize("lanes", sorted(LANES))
@pytest.mark.parametrize("blocks", sorted(BLOCK_BYTES))
@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_kernel_steps_one_layer_as_the_jnp_form(family, blocks, lanes,
                                                monkeypatch):
    monkeypatch.setattr(ssm_step, "BLOCK_BYTES", BLOCK_BYTES[blocks])
    s = _sizes(family)
    view = _view(s, LANES[lanes])
    operands = _operands(s, view)
    before = np.asarray(view.buffers["ssm"])
    want, want_y = ssm_step.ssm_step_reference(view.buffers["ssm"], LAYER,
                                               *operands)
    want_state = want[LAYER]
    buf, y = ssm_step.ssm_step(view.buffers["ssm"], LAYER, *operands,
                               interpret=True)
    buf, owned = np.asarray(buf), np.asarray(view.owned)
    assert owned.tolist() == [i in LANES[lanes] for i in range(SLOTS)]
    # an owned slot's new state and every slot's y to float32 rounding
    np.testing.assert_allclose(buf[LAYER][owned],
                               np.asarray(want_state)[owned], rtol=1e-6,
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want_y),
                               rtol=1e-6, atol=1e-5)
    # a slot no lane owns is written back as read, and no other layer of
    # the stacked buffer is touched: to the bit
    assert np.array_equal(buf[LAYER][~owned], before[LAYER][~owned])
    others = [i for i in range(LAYERS) if i != LAYER]
    assert np.array_equal(buf[others], before[others])
    # a padded lane reads slot 0's y, as `from_slots` says
    back = np.asarray(view.from_slots(y))
    for lane, slot in enumerate(LANES[lanes]):
        assert np.array_equal(back[lane], np.asarray(y)[max(slot, 0)])


def _weights(s, D, seed=2):
    k = iter(jax.random.split(jax.random.PRNGKey(seed), 8))

    def normal(*shape, scale=0.1):
        return scale * jax.random.normal(next(k), shape, jnp.float32)

    return {"in_proj": normal(D, 2 * s.d_inner + 2 * s.groups * s.state
                              + s.heads),
            "conv_w": normal(s.conv_kernel, s.conv_dim, scale=0.5),
            "conv_b": normal(s.conv_dim), "dt_bias": normal(s.heads),
            "A_log": normal(s.heads), "D": normal(s.heads, scale=1.0),
            "gate_norm": 1.0 + normal(s.d_inner),
            "out_proj": normal(s.d_inner, D)}


@pytest.mark.parametrize("lanes", sorted(LANES))
@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_step_is_the_same_mixer_on_either_path(family, lanes, monkeypatch):
    """`mamba2.step` whole, the path the chip takes (the predicate patched
    true, the kernel under the interpreter) against the CPU's: the mixer's
    output for every lane, the padded one too, and every part of the state
    left behind."""
    s, D = _sizes(family), 16
    p = _weights(s, D)
    h = jax.random.normal(jax.random.PRNGKey(3), (len(LANES[lanes]), D))
    want_view = _view(s, LANES[lanes])
    assert not ssm_step.steps_by_kernel(want_view.layout)  # the CPU's path
    want = mamba2.step(h, p, s, want_view, LAYER)
    monkeypatch.setattr(ssm_step, "steps_by_kernel", lambda layout: True)
    monkeypatch.setattr(ssm_step, "ssm_step", functools.partial(
        ssm_step.ssm_step, interpret=True))
    view = _view(s, LANES[lanes])
    got = mamba2.step(h, p, s, view, LAYER)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)
    for name, buf in want_view.buffers.items():
        np.testing.assert_allclose(
            np.asarray(view.buffers[name], np.float32),
            np.asarray(buf, np.float32), rtol=1e-6, atol=1e-6, err_msg=name)
    unowned = ~np.asarray(view.owned)
    assert np.array_equal(np.asarray(view.buffers["ssm"])[:, unowned],
                          np.asarray(_view(s, LANES[lanes]).buffers["ssm"])
                          [:, unowned])


def _layout(parts):
    return StateLayout(2, 4, parts)


@pytest.mark.parametrize("name,parts,backend,by_kernel", [
    ("the chip", _sizes("one-group").state_parts(), "tpu", True),
    ("several groups", _sizes("four-groups").state_parts(), "tpu", True),
    ("no chip", _sizes("one-group").state_parts(), "cpu", False),
    ("no SSM part (lfm2)", (("conv0", (64,), jnp.bfloat16),), "tpu", False),
    ("a bf16 state", (("ssm", (4, 8, 128), jnp.bfloat16),), "tpu", False),
    ("a state of half a lane tile", (("ssm", (4, 8, 64), jnp.float32),),
     "tpu", False),
    ("heads of half a sublane tile", (("ssm", (4, 4, 128), jnp.float32),),
     "tpu", False),
])
def test_the_path_is_a_matter_of_what_the_code_sees(name, parts, backend,
                                                    by_kernel, monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert ssm_step.steps_by_kernel(_layout(parts)) is by_kernel


def test_a_mesh_of_several_chips_keeps_the_jnp_form(cpu_mesh8, monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    layout = _layout(_sizes("one-group").state_parts())
    assert ssm_step.steps_by_kernel(layout)
    with jax.set_mesh(cpu_mesh8):
        assert not ssm_step.steps_by_kernel(layout)


@pytest.mark.parametrize("name,slots,R,block", [
    # granite-4.0-h-small: 128 heads of one group -> 32 heads of a slot
    ("granite_hybrid", 64, 128, (1, 32)),
    # nemotron_h: 8 heads a group -> a group's heads of 4 slots
    ("nemotron_h", 32, 8, (4, 8)),
    ("three slots", 3, 2, (1, 2)),
])
def test_a_block_is_heads_of_one_group_under_the_byte_target(name, slots, R,
                                                             block):
    rows, heads = ssm_step.block_of(slots, R, 64, 128)
    assert (rows, heads) == block
    assert R % heads == 0 and slots % rows == 0
    assert rows * heads * 64 * 128 * 4 <= ssm_step.BLOCK_BYTES
