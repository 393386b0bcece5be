"""Which device operations of a trace are a hyper-connected residual
path's coefficient maps (`ray_tpu/models/xing4.py` `mhc_coefficients`,
under `jax.named_scope("resid.mhc.coef")`).

The labels `trace_reduce.load` keeps are `name opcode result`, so the
operations are told by what they return, from the configuration's sizes
(n = `hc_mult` streams, K = n^2 + 2n maps a half-layer):

- the **maps and Sinkhorn's iterations** are one Pallas kernel a
  half-layer, a custom call named `mhc_maps`;
- the **product with phi** and what is made of the maps' scale and bias
  return a float32 matrix of exactly K rows, `f32[K, rows]` (`f32[24,256]`
  in a chunk): a size nothing else in the program has in that place.

What the labels do NOT tell (benchmark/flops_mhc.py says it too): the
state's mean square returns `f32[rows]` like any norm's reduction, and
XLA:TPU fuses the two mixes (`resid.mhc.pre`, `resid.mhc.post`) into the
products before and after them, which return `bf16[rows, C]` a stream
like every projection; their time is counted with those products', so
this reading is a floor of the residual path's share.

A configuration without `hc_mult` is not this reading's: None.
"""

from __future__ import annotations

import re

from benchmark import flops_mhc, mla_dense_ops, trace_reduce

_MATRIX = re.compile(r"f32\[(\d+),(\d+)\]")


def applies(config: dict) -> bool:
    return "hc_mult" in config and "hidden_size" in config \
        and "engine" in config


def maps_ops(events, config: dict) -> dict | None:
    """{"kernel": (seconds, calls), "product": (seconds, operations)} on
    the first device. None without a device plane or the sizes."""
    planes = trace_reduce.device_planes(events or [])
    if not applies(config) or not planes:
        return None
    K = config["hc_mult"] * (config["hc_mult"] + 2)
    kernel, product = [0.0, 0], [0.0, 0]
    for e in events:
        if e.plane != planes[0] or e.line != trace_reduce.OPS_LINE \
                or trace_reduce.opcode_of(e.name) in trace_reduce.CONTAINERS:
            continue
        if e.name.startswith("mhc_maps"):
            into = kernel
        elif any(int(m.group(1)) == K for m in _MATRIX.finditer(e.name)):
            into = product
        else:
            continue
        into[0] += e.dur_ns / 1e9
        into[1] += 1
    return {"kernel": tuple(kernel), "product": tuple(product)}


def seconds(found: dict) -> float:
    return found["kernel"][0] + found["product"][0]


def least_seconds(observed: dict) -> float | None:
    """The least seconds the chip could take for the maps of the programs
    the trace holds (`mla_dense_ops.programs`: the same trace's decode
    steps and chunks), each at the window's mean rows of a program of its
    kind."""
    cfg = observed["config"]
    loops = mla_dense_ops.dense_ops(observed.get("events"), cfg)
    mean = mla_dense_ops.window_means(observed)
    if not loops or mean is None:
        return None
    steps, chunks = mla_dense_ops.programs(loops, cfg)
    kind = observed["device_kind"]
    return sum(n * flops_mhc.program_least_seconds(
        cfg, mean[p]["rows"], kind)[0]
        for p, n in (("decode", steps), ("prefill", chunks)))
