"""One step of a Mamba-2 layer's recurrence over every lane slot's state,
one Pallas TPU kernel a layer over the state buffer where it lies.

`models/mamba2.py` `step` hands a decode program's recurrence to
`ssm_step` below where its predicate (`steps_by_kernel`) allows; elsewhere
it keeps its jnp form. The kernel

- takes the SSM part of the state whole, as it lies (`cache.StateLayout`:
  ``f32[layers, slots, H, P, N]``), aliased to its FIRST result, and
  touches only the blocks of layer `layer` (by scalar prefetch): the grid runs
  over (slot blocks, head blocks) of that one layer, a block of
  `block_of` slots x heads whose heads lie inside one group, so the group
  is the block's index and a family of one group and one of eight run the
  same kernel;
- per ``(P, N)`` tile of a slot's head: ``S_new = decay * S + xdt (x) B``
  stored to the aliased block and ``y = sum(S_new * C, -1)`` reduced while
  the tile is still on the chip, so a layer's state is read from HBM once
  and written once a step (the XLA program read it twice: a reduce does
  not fuse with an in-place dynamic-update-slice, PERF.md section 6, PR
  49);
- keeps the jnp form's arithmetic: float32 elementwise on the VPU and a
  float32 reduce over N, no MXU product. A slot no lane owns comes with
  ``decay = 1, xdt = 0``, and ``1 * S + 0`` is S to the bit.

`decay` (slots, H), `xdt` (slots, H, P), `B` and `C` (slots, G, N) come in
slot order (`cache.StateView.to_slots`); y (slots, H, P) goes back the
same way. ``interpret=True`` runs the kernel through the Pallas
interpreter on the CPU (the tests); `ssm_step_reference` is its oracle,
the jnp form on the same operands.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# the part of a layer's state this steps, as `Mamba2Sizes.state_parts`
# names it
PART = "ssm"

# State bytes a grid step reads (and writes). On the v5e the kernel moves a
# layer's state at the rate a copy through VMEM does from a MB a block up
# (646-650 GB/s at 1 and 2 MB; 620 at 512 KB, 528 at 256 KB: a step costs a
# fraction of a microsecond beside its bytes), and in and out
# double-buffered four blocks of a MB stay far under the scoped VMEM
# (PERF.md section 6, PR 49)
BLOCK_BYTES = 1024 * 1024


def steps_by_kernel(layout) -> bool:
    """Whether a decode program steps the recurrence of a state laid out
    as `layout` (`cache.StateLayout`) with the kernel and not with the jnp
    form: a float32 SSM part whose ``(P, N)`` is whole tiles of the chip's
    memory, buffers that lie whole on one chip (one device under the mesh
    in force), on a TPU. The one place that picks the path: `mamba2.step`
    asks it for the program it traces, the runner for what it counts."""
    part = next((p for p in layout.parts if p[0] == PART), None)
    if part is None:
        return False
    (_, P, N), dtype = part[1], part[2]
    return (jax.default_backend() == "tpu" and dtype == jnp.float32
            and P % 8 == 0 and N % 128 == 0
            and jax.sharding.get_abstract_mesh().size <= 1)  # 0: no mesh


def block_of(slots: int, R: int, P: int, N: int) -> tuple[int, int]:
    """(slots, heads) of a block: the most heads of one group (a power of
    two that divides R) under `BLOCK_BYTES`, then the most slots."""
    tile = P * N * 4
    heads = 1
    while R % (2 * heads) == 0 and 2 * heads * tile <= BLOCK_BYTES:
        heads *= 2
    rows = 1
    while slots % (2 * rows) == 0 and 2 * rows * heads * tile <= BLOCK_BYTES:
        rows *= 2
    return rows, heads


def _step_kernel(layer_ref, d_ref, x_ref, b_ref, c_ref, s_ref, o_ref, y_ref):
    # decay is a number a head and x dt a number a row of the head's
    # (P, N) tile: (rows, heads, 1 or P) -> (rows, heads, 1 or P, 1), down
    # the tile's lanes
    new = d_ref[...][..., None] * s_ref[...] \
        + x_ref[...][..., None] * b_ref[...][:, None]
    o_ref[...] = new
    y_ref[...] = jnp.sum(new * c_ref[...][:, None], axis=-1)


def ssm_step(buf, layer, decay, xdt, B, C, *, interpret: bool = False):
    """``S = decay S + xdt (x) B`` on every slot of layer `layer` (an int,
    or a traced i32) of `buf` (layers, slots, H, P, N) f32, in place, and
    ``y = S C`` -> (buf, y (slots, H, P) f32). decay (slots, H), xdt
    (slots, H, P), B and C (slots, G, N), all f32; heads ``g H / G .. (g +
    1) H / G`` share group g's B and C. The layer goes in by scalar
    prefetch and the call is a jitted function, so a program's Mamba
    layers share ONE traced and lowered kernel (a kernel a layer cost a
    decode program 0.2 s of its trace, seven programs a start: PERF.md
    section 6, PR 49)."""
    _, slots, H, P, N = buf.shape
    return _step(buf, layer, decay, xdt, B, C, interpret=interpret,
                 block=block_of(slots, H // B.shape[1], P, N))


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def _step(buf, layer, decay, xdt, B, C, *, block, interpret):
    """`ssm_step` at blocks of `block` (slots, heads), jitted."""
    _, slots, H, P, N = buf.shape
    R = H // B.shape[1]
    rows, heads = block
    state = pl.BlockSpec((None, rows, heads, P, N),
                         lambda i, j, layer: (layer[0], i, j, 0, 0))
    # a block's heads as a dimension of their own: a block of the small
    # operands is then whole in its last two dimensions at any `heads`
    a_head = pl.BlockSpec((rows, None, heads, 1),
                          lambda i, j, layer: (i, j, 0, 0))
    a_row = pl.BlockSpec((rows, None, heads, P),
                         lambda i, j, layer: (i, j, 0, 0))
    a_group = pl.BlockSpec((rows, None, 1, N),
                           lambda i, j, layer: (i, j * heads // R, 0, 0))
    buf, y = pl.pallas_call(
        _step_kernel,
        out_shape=(jax.ShapeDtypeStruct(buf.shape, buf.dtype),
                   jax.ShapeDtypeStruct((slots, H // heads, heads, P),
                                        jnp.float32)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(slots // rows, H // heads),
            in_specs=[a_head, a_row, a_group, a_group, state],
            out_specs=(state, a_row)),
        input_output_aliases={5: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        name="ssm_step",
        interpret=interpret,
    )(jnp.reshape(jnp.asarray(layer, jnp.int32), (1,)),
      decay.reshape(slots, H // heads, heads, 1),
      xdt.reshape(slots, H // heads, heads, P), B[:, :, None], C[:, :, None],
      buf)
    return buf, y.reshape(slots, H, P)


def ssm_step_reference(buf, layer: int, decay, xdt, B, C):
    """The kernel's oracle (tests, the chip's selftest), same operands:
    `mamba2.step`'s jnp form, the new state stored by a
    dynamic-update-slice and the read-out a consumer of its own."""
    _, slots, H, P, N = buf.shape
    G = B.shape[1]
    R = H // G
    new = decay.reshape(slots, G, R, 1, 1) \
        * buf[layer].reshape(slots, G, R, P, N) \
        + xdt.reshape(slots, G, R, P, 1) * B[:, :, None, None, :]
    y = jnp.sum(new * C[:, :, None, None, :], axis=-1)
    return (buf.at[layer].set(new.reshape(slots, H, P, N)),
            y.reshape(slots, H, P))
