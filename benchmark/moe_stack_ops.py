"""Which device operations of a trace are the HELD experts' where the
attention's output projection is as large as an expert layer's down
projection, so that `flops_moe_held.held_expert_ops` cannot tell the two
by result and time (GLM-5: 64 heads x 256 = 8 experts x 2,048 = 16,384
lanes into 6,144; PERF.md section 6, PR 40, finding 5).

Told by what only the experts have, and by the order the data forces:

- the held experts' gate and up products are STACKED over the experts:
  their result is 3-D, `bf16[E, rows, F]` or `bf16[E, F, rows]` (E experts
  held, F an expert's width). No other operation of the model returns E
  leading a dimension of F;
- their down products and the weighted sum over the experts are fused
  into one operation that returns the residual stream's shape, `bf16[rows,
  D]` (at few rows XLA fuses one of gate / up into it as well). It needs
  the stacked results, and the next layer's output projection needs IT,
  so it is the FIRST operation after a stacked product that returns
  `[rows, D]`, alone or in a tuple, and lasts at least half as long as
  that stacked product (the shared expert's down projection also returns
  `[rows, D]` and may be scheduled between the two: it is an eighth of
  the work, whatever the rows).

One such operation is one layer call of `rows` rows. The router and the
shared expert are not counted.
"""

from __future__ import annotations

import re
from collections import defaultdict

from benchmark import trace_reduce

_RESULTS = re.compile(r"\b([a-z]+[0-9]+)\[([0-9,]*)\]")


def _results(label: str) -> list[tuple[str, tuple[int, ...]]]:
    """(dtype, dims) of every result an operation's label names (the
    label is `name opcode result`; a tuple's results are all there)."""
    shapes = label.split(" ", 2)[2] if label.count(" ") >= 2 else ""
    return [(m.group(1), tuple(int(x) for x in m.group(2).split(",") if x))
            for m in _RESULTS.finditer(shapes)]


def held_stack_ops(events, held: int, d_ff: int, d_model: int) -> dict | None:
    """{rows: (seconds, layer calls)} of the held experts' operations on
    the first device: the stacked products and the operation their down
    products and weighted sum are fused into. None without a device plane
    or without a whole layer call."""
    planes = trace_reduce.device_planes(events or [])
    if not planes:
        return None
    ops = sorted((e for e in events if e.plane == planes[0]
                  and e.line == trace_reduce.OPS_LINE
                  and trace_reduce.opcode_of(e.name)
                  not in trace_reduce.CONTAINERS),
                 key=lambda e: e.start_ns)
    found = defaultdict(lambda: [0.0, 0])
    open_rows, open_ns, stacked = None, 0.0, 0.0
    for e in ops:
        results = _results(e.name)
        first = results[0] if results else ("", ())
        if first[0] == "bf16" and len(first[1]) == 3 \
                and first[1][0] == held and d_ff in first[1][1:]:
            dims = first[1]
            rows = dims[1] if dims[2] == d_ff else dims[2]
            if open_rows != rows:  # a call whose end the trace cut off
                stacked = 0.0
            open_rows, open_ns = rows, e.dur_ns
            stacked += e.dur_ns
        elif open_rows is not None and e.dur_ns >= 0.5 * open_ns and any(
                r == ("bf16", (open_rows, d_model)) for r in results):
            found[open_rows][0] += (stacked + e.dur_ns) / 1e9
            found[open_rows][1] += 1
            open_rows, stacked = None, 0.0
    return {k: tuple(v) for k, v in found.items()} or None
