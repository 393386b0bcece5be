"""Operations and bytes the HELD share of a routed-expert layer requires:
the chosen (token, expert) pairs that landed on experts this chip holds,
and those of its experts that received any. Experts here are not gated:
two matrices each (up, down), `benchmark/flops_moe.py` counts SwiGLU's
three. What a dispatch computes or reads beyond the pairs (every held
expert for every row) is the program's choice and does not count.
"""

from __future__ import annotations

from collections import defaultdict

from benchmark.flops import least_seconds, peaks
from benchmark.flops_moe import moe_counters
from benchmark.ssm_ops import device_results

MATRICES = 2  # up, down: each d_model x d_ff


def held_layer_flops(pairs: float, d_model: int, d_ff: int) -> float:
    return pairs * MATRICES * 2.0 * d_model * d_ff


def held_layer_bytes(touched: float, rows: float, d_model: int, d_ff: int,
                     itemsize: int = 2) -> float:
    """The weights of the held experts that received a pair, once, and
    the layer's rows in and out, once."""
    return touched * MATRICES * d_model * d_ff * itemsize \
        + 2.0 * rows * d_model * itemsize


def held_layer_least_seconds(pairs: float, touched: float, rows: float,
                             d_model: int, d_ff: int, device_kind: str
                             ) -> tuple[float, str]:
    return least_seconds(held_layer_flops(pairs, d_model, d_ff),
                         held_layer_bytes(touched, rows, d_model, d_ff),
                         device_kind)


def held_counters(observed: dict, kind: str) -> dict | None:
    """after - before of what the engine's routing account says of the
    experts held here, for one step kind: `pairs` (all experts),
    `held_pairs`, `held_experts_touched`, `layer_calls`. None where the
    program has no such counters (a parent without them, a dense model)."""
    c = moe_counters(observed, kind)
    if c is None:
        return None
    a = observed["after"]["stats"]["moe"][kind]
    b = (observed["before"]["stats"].get("moe") or {}).get(kind) or {}
    if "held_pairs" not in a:
        return None
    for key in ("held_pairs", "held_experts_touched"):
        c[key] = a[key] - b.get(key, 0)
    return c


def held_expert_ops(events, held: int, d_ff: int, d_model: int,
                    device_kind: str) -> dict | None:
    """{rows: (seconds, layer calls)} of the held experts' operations on
    the first device. With E experts held, of width F, at hidden size D,
    the stacked products return `bf16[E, F, rows]` / `bf16[E, rows, F]`
    (up, with the squared relu) and `bf16[E, rows, D]` / `bf16[E, D, rows]`
    (down), and the weighted sum over experts returns `bf16[rows, D]`, the
    residual stream's shape. At few rows XLA fuses the whole layer into
    that last operation, and many other operations return that shape, so
    one counts only where it could not be anything else: where it takes
    longer than a quarter of the held experts' weights take to read at the
    HBM peak (every other producer of `[rows, D]` reads at most the shared
    expert, a 32nd of them). Each such operation is one layer call of
    `rows` rows."""
    floor_ns = 0.25e9 * held_layer_bytes(
        held, 0, d_model, d_ff) / peaks(device_kind)["hbm_bytes_per_s"]
    found = defaultdict(lambda: [0.0, 0])
    for e, dtype, dims in device_results(events):
        if dtype != "bf16":
            continue
        if len(dims) == 3 and dims[0] == held and (
                d_ff in dims[1:] or d_model in dims[1:]):
            rows = dims[1] if dims[2] in (d_ff, d_model) else dims[2]
            found[rows][0] += e.dur_ns / 1e9
        elif len(dims) == 2 and dims[1] == d_model \
                and e.dur_ns >= floor_ns:
            found[dims[0]][0] += e.dur_ns / 1e9
            found[dims[0]][1] += 1
    return {k: tuple(v) for k, v in found.items() if v[1]} or None
