"""Which device operations of a trace read a lane's cached context
(`ray_tpu/ops/context_attention.py` `attend_cached`), in a model with
kinds of KV layer (full and window attention).

The program marks the read with `jax.named_scope("attn.ctx_read")`, but
the labels `trace_reduce.load` keeps are `name opcode result`, so the
operations are told by what they return, as `moe_ops.py` and `ssm_ops.py`
tell theirs, from the configuration's sizes (H query heads on HK KV heads
of a kind, R = H / HK, V heads of width Dv, a window of W, pages of
`block_size`):

- a **full kind** reads in tiles under a `while`, one a group of lanes
  and layer, whose carry is the running softmax: `(s32[], f32[G,HK,R,T],
  f32[G,HK,R,T], f32[G,T,HK,R,Dv], ...)` for G lanes of T rows. The
  loop's own event covers its body, so its duration is the read's and
  its softmax's device time; a decode program's first loop a layer has
  all its lanes (G = `max_batch_size`), a chunk's one loop has G = 1 and
  T > 1, which counts the programs;
- a **window kind** reads one tile a lane with no loop: `window_slots` =
  (max(0, W - 2) // block_size + 2) x block_size slots (144 at W = 128
  and pages of 16), a size nothing else in the program has. Every
  operation with a result that has a dimension of `window_slots` counts
  (the gathered K tile `bf16[B,144,HK,D]`, the scores and their
  reductions `bf16[B,HK,144,R]`, the masks).

A configuration without these keys is not this reading's: None.
"""

from __future__ import annotations

import re
from collections import defaultdict

from benchmark import trace_reduce

SIZE_KEYS = ("hybrid_layer_pattern", "num_hidden_layers",
             "num_attention_heads", "num_key_value_heads",
             "swa_num_key_value_heads", "head_dim", "v_head_dim",
             "sliding_window")
_LOOP = re.compile(
    r"\(s32\[\], f32\[(\d+),(\d+),(\d+),(\d+)\], f32\[\1,\2,\3,\4\], "
    r"f32\[\1,\4,\2,\3,(\d+)\]")
_SHAPE = re.compile(r"\[([0-9,]+)\]")


def kinds_of(config: dict) -> list[dict] | None:
    """The kinds of KV layer a configuration file describes: name, layers
    held, KV heads, bytes of a token's K and V rows in one layer (bf16),
    window (None: full) and the slots of its one tile."""
    if any(k not in config for k in SIZE_KEYS) or "engine" not in config:
        return None
    pattern = config["hybrid_layer_pattern"][:config["num_hidden_layers"]]
    page = config["engine"]["block_size"]
    out = []
    for name, value, heads, window in (
            ("full", 0, config["num_key_value_heads"], None),
            ("window", 1, config["swa_num_key_value_heads"],
             config["sliding_window"])):
        out.append({
            "name": name, "layers": pattern.count(value), "heads": heads,
            "row_bytes": 2 * heads * (config["head_dim"]
                                      + config["v_head_dim"]),
            "window": window,
            "window_slots": None if window is None
            else (max(0, window - 2) // page + 2) * page})
    return out


def ctx_ops(events, config: dict) -> dict | None:
    """{"loops": {(G, T): (seconds, loops)}, "window": seconds} on the
    first device: the full kinds' tile loops by lanes and rows, and the
    window kinds' operations. None without a device plane or where the
    configuration has no kinds."""
    kinds = kinds_of(config)
    planes = trace_reduce.device_planes(events or [])
    if kinds is None or not planes:
        return None
    H, Dv = config["num_attention_heads"], config["v_head_dim"]
    full_heads = {k["heads"] for k in kinds if k["window"] is None}
    tiles = {k["window_slots"] for k in kinds if k["window"] is not None}
    loops = defaultdict(lambda: [0.0, 0])
    window = 0.0
    for e in events:
        if e.plane != planes[0] or e.line != trace_reduce.OPS_LINE:
            continue
        if trace_reduce.opcode_of(e.name) == "while":
            m = _LOOP.search(e.name)
            if m:
                G, HK, R, T, dv = map(int, m.groups())
                if HK in full_heads and HK * R == H and dv == Dv:
                    loops[(G, T)][0] += e.dur_ns / 1e9
                    loops[(G, T)][1] += 1
        elif trace_reduce.opcode_of(e.name) not in trace_reduce.CONTAINERS:
            if any(t in map(int, dims.split(","))
                   for dims in _SHAPE.findall(e.name) for t in tiles):
                window += e.dur_ns / 1e9
    return {"loops": {k: tuple(v) for k, v in loops.items()},
            "window": window}
