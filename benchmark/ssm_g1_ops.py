"""Which device operations of a trace are a ONE-GROUP Mamba-2 mixer's
recurrence (granite-4.0-h-small: H = 128 heads of P = 64 over a state of
N = 128, G = 1, so every head reads the same B and C).

`benchmark/ssm_ops.py` tells nemotron_h's by results that carry G among
their dimensions; at one group XLA drops the dimension of 1, and with
H = N = 128 a result's dimensions alone do not say which is which. The
trace's labels are `name opcode result` and a fusion's name says nothing
of the scope it came from (`ssm.scan` / `ssm.step`), so the operations are
told by the results that one group and these sizes leave unambiguous,
dimensions of 1 dropped (`lanes` slots, L layers with state, a chunk or a
shorter prompt's bucket of q rows, a power of two from 16 to Q):

- **step** (decode): the in-place update of every slot's state, a result
  `f32[L, lanes, H, P, N]`, and what goes with it in slot order: float32
  results of exactly `[lanes, H, P]` (x dt in, the read-out y, which
  reads the state again where it is not fused with the update),
  `[lanes, H P]` and `[lanes, H]` (dt, the decay, B and C: N = H). A
  prompt's or a chunk's
  program writes ONE lane's state into the same buffer, and that write
  returns the same shape: it is told apart by its time, which is under a
  quarter of what reading and writing `lanes` slots' state takes at the
  HBM peak (a step cannot be), and counted with the scan;
- **scan** (a prompt or a chunk): the masked product `f32[q, 1, H, P]`
  (its group dimension kept: one a chunk and layer, it counts the chunks),
  and float32 (or a bf16 copy beside it) results whose dimensions, in any
  order, are `[q, q]` (C B^T and the decay between rows), `[H, P, q]` (the
  carried state's read-out), `[q, H P]`, `[q, H]` (x dt, the decay's
  running sums), `[H, P, N]` (the lane's state read, and written). A
  prompt whose bucket q is `lanes` rows returns a few of the step's small
  shapes: microseconds, counted with the step.

The convolution, the gate's norm and the two projections are not the
recurrence's and are not counted. A trace with neither a step nor a
chunk is one this reading does not understand: None.
"""

from __future__ import annotations

from collections import defaultdict

from benchmark.flops import peaks
from benchmark.flops_ssm import state_bytes
from benchmark.ssm_ops import device_results

SIZE_KEYS = ("mamba_n_heads", "mamba_d_head", "mamba_d_state",
             "mamba_n_groups", "mamba_chunk_size", "layer_types",
             "num_hidden_layers")


def sizes_of(config: dict) -> dict | None:
    """The mixer's sizes off a configuration file; None where the file
    has not these keys or the mixer has more than one group."""
    if any(k not in config for k in SIZE_KEYS) \
            or config["mamba_n_groups"] != 1:
        return None
    kinds = config["layer_types"][:config["num_hidden_layers"]]
    return {"H": config["mamba_n_heads"], "P": config["mamba_d_head"],
            "N": config["mamba_d_state"], "G": 1,
            "Q": config["mamba_chunk_size"], "L": kinds.count("mamba"),
            "lanes": config["engine"]["max_batch_size"]}


def ssm_ops(events, s: dict, device_kind: str) -> dict | None:
    """{"step": (seconds, state updates), "scan": (seconds, {q: chunks of
    q rows})} on the first device. A state update is one layer of one
    decode step; a chunk is one pass of one layer's chunked form."""
    H, P, N, Q, L, lanes = (s[k] for k in "H P N Q L lanes".split())
    rows = [q for q in (16, 32, 64, 128, 256, 512, 1024) if q <= Q]
    floor_ns = 0.25e9 * state_bytes(lanes, H, P, N) \
        / peaks(device_kind)["hbm_bytes_per_s"]
    in_step = {(lanes, H, P), (lanes, H * P), (lanes, H), (lanes, N)}
    in_scan = {tuple(sorted(shape)) for q in rows for shape in (
        (H, P, q), (H * P, q), (H, q), (q, q))} | {tuple(sorted((H, P, N)))}
    step, updates, scan = 0.0, 0, 0.0
    chunks = defaultdict(int)
    for e, dtype, dims in device_results(events):
        if dtype not in ("f32", "bf16"):
            continue
        sec = e.dur_ns / 1e9
        if dims == (L, lanes, H, P, N):
            if e.dur_ns >= floor_ns:
                step += sec
                updates += 1
            else:
                scan += sec
        elif dtype == "f32" and dims in in_step:
            step += sec
        elif len(dims) == 4 and dims[1:] == (1, H, P) and dims[0] in rows:
            scan += sec  # the masked product: one a chunk and layer
            chunks[dims[0]] += 1
        elif tuple(sorted(d for d in dims if d != 1)) in in_scan:
            scan += sec
    if not updates and not chunks:
        return None
    return {"step": (step, updates), "scan": (scan, dict(chunks))}


def from_observed(observed: dict) -> dict | None:
    s = sizes_of(observed["config"])
    if s is None:
        return None
    return ssm_ops(observed.get("events"), s, observed["device_kind"])
