"""Which device operations of a trace are a latent kind's DENSE read
(`ray_tpu/ops/context_attention.py` `attend_latent`: latent attention with
no indexer, every cached slot of a lane to its length), which programs
the trace holds, and the kind's counters in the window.

The program marks the read with `jax.named_scope("attn.mla.dense")`, but
the labels `trace_reduce.load` keeps are `name opcode result`, so the
operations are told by what they return, as `dsa_ops.py` tells the other
latent kind's: the read is one `while` a group of lanes and layer whose
carry is the running softmax over the latent tiles, ``(s32[], f32[b,1,H,T],
f32[b,1,H,T], f32[b,T,1,H,R], ...)`` for b lanes of T rows, H heads on a
latent of R lanes (a chunk's: b = 1 and T > 1; a decode step's: T = 1 and
b the step's rows, then the rows less a group, ...). A `while`'s own event
covers its body. The start of the softmax on the program's own rows, the
projections and the value up-projection are not counted.

The programs: a chunk has ONE loop a layer. A decode step of S rows (a
power of two) has one loop a group of `lanes_per_group(S)` lanes and
layer, over S, S - g, ..., g rows: a loop over exactly 4 rows is in every
step of 4 rows or more, once a layer (groups of 1, 2, 2, 4 lanes at 4, 8,
16, 32 rows), and a step of 1 or 2 rows has a loop over 1 row where a
step of 4 has one over 1 and one over 3.

A configuration with an indexer (`index_topk`) is `dsa_ops.py`'s, one
without latent attention nobody's: None.
"""

from __future__ import annotations

import re
from collections import defaultdict

from benchmark import flops_mla_dense, trace_reduce
from benchmark.readers import counter_delta

SIZE_KEYS = ("kv_lora_rank", "qk_rope_head_dim", "qk_nope_head_dim",
             "v_head_dim", "num_attention_heads", "num_hidden_layers")
_LOOP = re.compile(
    r"\(s32\[\], f32\[(\d+),1,(\d+),(\d+)\], f32\[\1,1,\2,\3\], "
    r"f32\[\1,\3,1,\2,(\d+)\]")


def applies(config: dict) -> bool:
    return all(k in config for k in SIZE_KEYS) \
        and "index_topk" not in config and "engine" in config


def dense_ops(events, config: dict) -> dict | None:
    """{(b, T): (seconds, loops)} on the first device: the read's loops by
    the lanes and rows of their carry. None without a device plane or
    where the configuration has no such kind."""
    planes = trace_reduce.device_planes(events or [])
    if not applies(config) or not planes:
        return None
    H, R = config["num_attention_heads"], config["kv_lora_rank"]
    loops = defaultdict(lambda: [0.0, 0])
    for e in events:
        if e.plane != planes[0] or e.line != trace_reduce.OPS_LINE \
                or trace_reduce.opcode_of(e.name) != "while":
            continue
        m = _LOOP.search(e.name)
        if m and (int(m.group(2)), int(m.group(4))) == (H, R):
            key = (int(m.group(1)), int(m.group(3)))
            loops[key][0] += e.dur_ns / 1e9
            loops[key][1] += 1
    return {k: tuple(v) for k, v in loops.items()}


def seconds(found: dict) -> float:
    return sum(s for s, _ in found.values())


def programs(found: dict, config: dict) -> tuple[float, float]:
    """(decode steps, chunks) in the trace, from the loops' carries (the
    module's docstring says how)."""
    layers = config["num_hidden_layers"]

    def n(b, T=1):
        return found.get((b, T), (0.0, 0))[1]

    steps = (n(4) + n(1) - n(3)) / layers
    chunks = sum(c for (b, T), (_, c) in found.items()
                 if b == 1 and T > 1) / layers
    return steps, chunks


def counters(observed: dict) -> dict | None:
    """{program: after - before of
    `engine_stats()["context_by_kind"][kind][program]`} for the latent
    kind that is read whole, inside the window. None where the program
    has no such kind or no such counters (the parent)."""
    if not observed.get("before") or not observed.get("after"):
        return None
    after, before = (observed[k]["stats"] for k in ("after", "before"))
    kind = next((name for name, kv in (after.get("kv") or {}).items()
                 if kv.get("latent") and not kv.get("select")), None)
    by = (after.get("context_by_kind") or {}).get(kind)
    if not by or "row_slots" not in by.get("decode", {}):
        return None
    was = (before.get("context_by_kind") or {}).get(kind) or {}
    return {program: {k: v - (was.get(program) or {}).get(k, 0)
                      for k, v in now.items()}
            for program, now in by.items()}


def window_means(observed: dict) -> dict | None:
    """{"decode": {...}, "prefill": {...}}: what ONE decode step and ONE
    chunk launch did over the window, in the mean: `rows` real rows,
    `pairs` (row, cached slot) pairs, `slots` cached slots below its
    lanes' lengths."""
    cfg = observed["config"]
    moved = counters(observed)
    steps = counter_delta(observed, "steps", "decode")
    if not moved or not steps:
        return None
    page = cfg["engine"]["block_size"]
    table_slots = -(-cfg["engine"]["max_model_len"] // page) * page
    calls = {"decode": steps,
             "prefill": moved["prefill"]["slots_full"] / table_slots}
    if not all(calls.values()):
        return None
    return {p: {"rows": moved[p]["rows"] / n,
                "pairs": moved[p]["row_slots"] / n,
                "slots": moved[p]["slots_valid"] / n}
            for p, n in calls.items()}


def busy_seconds(events) -> float:
    return sum(e - s for s, e in trace_reduce.busy_intervals(
        events, trace_reduce.device_planes(events)[0])) / 1e9


def least_seconds(observed: dict, found: dict) -> float | None:
    """The least seconds the chip could take for the dense reads of the
    programs the trace holds (`programs`), each at the window's mean of
    what a program of its kind had to read (`window_means`)."""
    cfg = observed["config"]
    mean = window_means(observed)
    if mean is None:
        return None
    steps, chunks = programs(found, cfg)
    kind = observed["device_kind"]
    return sum(n * flops_mla_dense.program_least_seconds(
        cfg, mean[p]["pairs"], mean[p]["slots"], kind)[0]
        for p, n in (("decode", steps), ("prefill", chunks)))
