"""Which device operations of a trace are a routed-expert layer's.

The program marks the layer's parts with `jax.named_scope` (`moe.route`,
`moe.dispatch`, `moe.experts`, `moe.combine`), and the compiled HLO carries
them as `op_name` metadata, but the labels `trace_reduce.load` keeps are
`name opcode result`, and XLA names a fusion `fusion.N` whatever its scope.
So the operations are told by what they return, as `flash_roofline_pct`
tells its kernels, from the configuration's sizes (E experts of width F,
hidden size D):

- an expert product: a result `bf16[E, a, b]` (the stacked experts'
  gate and up products, `[E, F, rows]` or `[E, rows, F]`);
- its consumer: the first later operation whose result is
  `bf16[rows, D]` — the down projection, which XLA fuses with the
  weighted sum over experts, so that the result has the residual
  stream's shape. Every operation with such a consumer's label counts;
- routing: results whose last dimension is E (`f32[rows, E]`, `s32[E]`).

(my chip run, PR 27: per layer of a 16-lane decode step `fusion.234
bf16[64,1024,16]` 0.391 ms and `fusion.240 bf16[16,2048]` 0.743 ms, the
three products' 805 MB in 1.134 ms.) A trace in which an expert product
has no consumer is one this reading does not understand: None.
"""

from __future__ import annotations

import re
from collections import defaultdict

from benchmark import trace_reduce

_RESULT = re.compile(r"^\S+ \S+ \(?([a-z0-9]+)\[([0-9,]*)\]")


def result_of(label: str) -> tuple[str, tuple[int, ...]] | None:
    """(dtype, dims) of an operation's (first) result, from its label."""
    m = _RESULT.match(label)
    if not m:
        return None
    return m.group(1), tuple(int(x) for x in m.group(2).split(",") if x)


def expert_ops(events, n_experts: int, d_ff: int, d_model: int) -> dict | None:
    """{"experts": {rows: [seconds, layer calls]}, "routing": seconds} for
    the first device of a trace; None without expert products, or when one
    has no consumer."""
    if not events:
        return None
    planes = trace_reduce.device_planes(events)
    if not planes:
        return None
    ops = sorted((e for e in events if e.plane == planes[0]
                  and e.line == trace_reduce.OPS_LINE
                  and trace_reduce.opcode_of(e.name)
                  not in trace_reduce.CONTAINERS),
                 key=lambda e: e.start_ns)
    results = [result_of(e.name) for e in ops]
    product_rows, consumers, wanted = {}, {}, None
    for e, r in zip(ops, results):
        if r is None:
            continue
        dtype, dims = r
        if dtype == "bf16" and len(dims) == 3 and dims[0] == n_experts \
                and d_ff in dims[1:]:
            rows = dims[1] if dims[2] == d_ff else dims[2]
            product_rows[e.name] = rows
            wanted = rows
        elif wanted is not None and dtype == "bf16" \
                and dims == (wanted, d_model):
            consumers[e.name] = wanted
            wanted = None
    if not product_rows or set(product_rows.values()) - set(consumers.values()):
        return None
    experts = defaultdict(lambda: [0.0, 0])
    routing = 0.0
    for e, r in zip(ops, results):
        if e.name in product_rows:
            experts[product_rows[e.name]][0] += e.dur_ns / 1e9
        elif e.name in consumers:
            experts[consumers[e.name]][0] += e.dur_ns / 1e9
            experts[consumers[e.name]][1] += 1
        elif r is not None and r[1] and r[1][-1] == n_experts \
                and len(r[1]) <= 2:
            routing += e.dur_ns / 1e9
    return {"experts": {k: tuple(v) for k, v in experts.items()},
            "routing": routing}


def from_observed(observed: dict) -> dict | None:
    cfg = observed["config"]
    if "num_experts" not in cfg:
        return None
    return expert_ops(observed.get("events"), cfg["num_experts"],
                      cfg["intermediate_size"], cfg["hidden_size"])
