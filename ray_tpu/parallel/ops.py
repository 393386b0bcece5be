"""In-program (SPMD) collectives over mesh axes.

This is the data-plane replacement for the reference's NCCL groups
(util/collective/collective_group/nccl_collective_group.py) and the
compiled-DAG channel collectives (experimental/channel/nccl_group.py):
inside a pjit/shard_map program, XLA lowers these to ICI collectives on
TPU — no process-level machinery at all. Use the host-side
ray_tpu.util.collective only for out-of-band CPU metadata.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ray_tpu.parallel.mesh import AXIS_DATA


# named_scope wrappers: collectives are in-trace (XLA lowers them), so
# they cannot be wall-timed from the host — the scope name is what lets
# the XLA/TPU profiler attribute collective time inside a step (the
# Podracer-style compile/collective/step breakdown; see OBSERVABILITY.md)


def psum(x, axis_name: str | tuple = AXIS_DATA):
    with jax.named_scope("rt.psum"):
        return jax.lax.psum(x, axis_name)


def pmean(x, axis_name: str | tuple = AXIS_DATA):
    with jax.named_scope("rt.pmean"):
        return jax.lax.pmean(x, axis_name)


def pmax(x, axis_name: str | tuple = AXIS_DATA):
    with jax.named_scope("rt.pmax"):
        return jax.lax.pmax(x, axis_name)


def all_gather(x, axis_name: str, *, axis: int = 0, tiled: bool = True):
    with jax.named_scope("rt.all_gather"):
        return jax.lax.all_gather(x, axis_name, axis=axis, tiled=tiled)


def reduce_scatter(x, axis_name: str, *, scatter_dimension: int = 0):
    with jax.named_scope("rt.reduce_scatter"):
        return jax.lax.psum_scatter(x, axis_name,
                                    scatter_dimension=scatter_dimension,
                                    tiled=True)


def all_to_all(x, axis_name: str, *, split_axis: int, concat_axis: int):
    return jax.lax.all_to_all(x, axis_name, split_axis=split_axis,
                              concat_axis=concat_axis, tiled=True)


def ppermute(x, axis_name: str, perm):
    return jax.lax.ppermute(x, axis_name, perm)


def ring_shift(x, axis_name: str, shift: int = 1):
    """Shift values around the axis ring (building block of ring
    attention / pipelined collectives)."""
    n = axis_size(axis_name)
    perm = [(i, (i + shift) % n) for i in range(n)]
    return jax.lax.ppermute(x, axis_name, perm)


def axis_index(axis_name: str):
    return jax.lax.axis_index(axis_name)


def axis_size(axis_name: str):
    return jax.lax.axis_size(axis_name)


_HLO_COLLECTIVES = {
    "all-reduce": "allreduce",
    "all-gather": "all_gather",
    "reduce-scatter": "reduce_scatter",
    "collective-permute": "collective_permute",
    "all-to-all": "all_to_all",
}


def collective_op_counts(hlo_text: str) -> dict[str, int]:
    """Count the collective ops in a compiled HLO module, keyed by the
    catalog's `op=` label names (allreduce/all_gather/reduce_scatter/
    collective_permute/all_to_all).

    This is the structural face of collective attribution: in-program
    collectives cannot be wall-timed from the host (XLA fuses and
    overlaps them), but the compiled program says exactly which ones a
    step pays for — e.g. a ZeRO-1 step trades the grad allreduce for
    reduce-scatter + param all-gather (on XLA:CPU the partitioner keeps
    allreduce + slice and the param all-gathers appear; on TPU it forms
    true reduce-scatter). Async pairs (`*-start`/`*-done`) count once.
    """
    import re

    out: dict[str, int] = {}
    for hlo_name, label in _HLO_COLLECTIVES.items():
        n = len(re.findall(rf"{hlo_name}(?:-start)?\(", hlo_text))
        if n:
            out[label] = n
    return out


def shard_map(f, mesh, in_specs, out_specs, check_vma: bool = False):
    """`jax.shard_map` with varying-manual-axes checking off by default:
    collective-heavy SPMD bodies (all_gather outputs, ring schedules)
    routinely produce values that are replicated at runtime but not
    statically inferable, and jax rejects those under check_vma."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)
