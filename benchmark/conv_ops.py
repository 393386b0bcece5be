"""Which device operations of a trace are a gated short-convolution
operator's (`ray_tpu/models/lfm2.py`: `in_proj`, the gate, the window's
sum and its carried rows, `out_proj`).

The program marks the operator's parts with `jax.named_scope`
(`conv.in_proj`, `conv.gate`, `conv.window`, `conv.out_proj`), but the
labels `trace_reduce.load` keeps are `name opcode result` and XLA names a
fusion `fusion.N` whatever its scope. So the operations are told by what
they return, and by the order the data forces, from the configuration's
sizes (hidden size D, a convolution over K rows, L layers that have one,
`lanes` slots, a router E wide, dense feed-forwards F wide):

- a call OPENS with `in_proj`'s product, the one computation of the
  model that returns `[rows, 3 D]` (a fusion, or a bare product: the
  asynchronous slices that fetch the weight itself return its shape and
  are no computation);
- it CLOSES at the block's feed-forward, which needs the operator's
  output: the first later COMPUTATION that returns a router's scores
  (`[rows, E]`), a dense feed-forward's `[rows, F]`, or an expert stack's
  3-D result (the asynchronous copy of the router's `s32[rows, E]`
  buffer is issued inside the operator and is no computation);
- in between, an operation is the operator's where one of its results is
  `[rows, D]` or `[rows + K - 1, D]` (the gate, the rows joined to the
  carried window, the sum, `out_proj`, which XLA fuses with the residual
  sum), the carried rows' buffer `[L, lanes, D]` (the window written
  back), a row or the taps alone (`[1, D]`, `[1, 1, D]`, `[K, D]`), or
  the END of an asynchronous fetch of a slice of `out_proj` (`slice-done`
  / `async-done` of `[D / n, D]`: the time the operator waited for its
  own weights; `in_proj`'s are waited for before the call opens, and
  what a fetch costs while other layers run is in no event: see
  `layer_metrics/conv_roofline_pct.py`). What else the scheduler put
  between the two (another layer's weights relaid, a norm's `[rows]`) is
  not counted.

One closed call is one layer's operator on `rows` rows; a call the trace
cut off is dropped. A trace with no `[rows, 3 D]` result is one this
reading does not understand: None.
"""

from __future__ import annotations

from collections import defaultdict

from benchmark import trace_reduce
from benchmark.moe_stack_ops import _results

PRODUCTS = ("fusion", "convolution", "dot")
WAITS = ("async-done", "slice-done", "copy-done")
SIZE_KEYS = ("conv_L_cache", "layer_types", "hidden_size",
             "intermediate_size", "num_experts_per_tok")


def sizes_of(config: dict) -> dict | None:
    if any(k not in config for k in SIZE_KEYS) or "engine" not in config:
        return None
    return {"D": config["hidden_size"], "K": config["conv_L_cache"],
            "L": list(config["layer_types"]).count("conv"),
            "lanes": config["engine"]["max_batch_size"],
            # the router's width is the PUBLISHED count of experts
            "E": config.get("published", config)["num_experts"],
            "F": config["intermediate_size"]}


def conv_ops(events, s: dict) -> dict | None:
    """{rows: (seconds, calls)} of the conv operators' operations on the
    first device."""
    planes = trace_reduce.device_planes(events or [])
    if not planes:
        return None
    D, K, L, lanes, E, F = (s[k] for k in "D K L lanes E F".split())
    ops = sorted((e for e in events if e.plane == planes[0]
                  and e.line == trace_reduce.OPS_LINE
                  and trace_reduce.opcode_of(e.name)
                  not in trace_reduce.CONTAINERS),
                 key=lambda e: e.start_ns)
    found = defaultdict(lambda: [0.0, 0])
    rows, pending, mine = None, 0.0, ()
    for e in ops:
        shapes = [dims for _, dims in _results(e.name)]
        opcode = trace_reduce.opcode_of(e.name)
        opened = next((d[0] for d in shapes
                       if len(d) == 2 and d[1] == 3 * D), None) \
            if opcode in PRODUCTS else None
        if opened is not None:
            rows, pending = opened, e.dur_ns
            mine = ((rows, D), (rows + K - 1, D), (L, lanes, D), (1, D),
                    (1, 1, D), (K, D))
        elif rows is None:
            continue
        elif opcode in PRODUCTS and any(
                (len(d) == 2 and d[0] == rows and d[1] in (E, F))
                or (len(d) == 3 and d[-1] != D and 1 not in d[:2])
                for d in shapes):
            found[rows][0] += pending / 1e9
            found[rows][1] += 1
            rows = None
        elif any(d in mine for d in shapes) or (
                opcode in WAITS and any(
                    len(d) == 2 and d[1] == D and D % d[0] == 0
                    for d in shapes)):
            pending += e.dur_ns
    return {k: tuple(v) for k, v in found.items()} or None


def from_observed(observed: dict) -> dict | None:
    s = sizes_of(observed["config"])
    if s is None:
        return None
    return conv_ops(observed.get("events"), s)
