"""Which device operations of a trace are a Mamba-2 mixer's.

The program marks the mixer's parts with `jax.named_scope` (`ssm.in_proj`,
`ssm.conv`, `ssm.scan` / `ssm.step`, `ssm.gate_norm`, `ssm.out_proj`), but
the labels `trace_reduce.load` keeps are `name opcode result` and XLA names
a fusion `fusion.N` whatever its scope. So the operations are told by what
they return, as `moe_ops.py` tells the experts', from the configuration's
sizes (H heads of P, a state of N, G groups of R = H / G heads, a
convolution over C = H P + 2 G N channels, `lanes` slots, L layers with
state, chunks of at most Q rows):

- **conv**: any result with a dimension of C;
- **step** (decode): the in-place update of every slot's state, a result
  `f32[L, lanes, H, P, N]`, and the small float32 operations over `lanes`
  rows that put the lanes' inputs in slot order and bring y back
  (`[lanes, H]`, and three or four dimensions of H, P, N, G, R alone). A
  prompt's or a chunk's program writes ONE lane's state into the same
  buffer, and that write returns the same shape: it is told apart by its
  time, which is under a quarter of what reading and writing `lanes`
  slots' state takes at the HBM peak (a step cannot be), and counted with
  the scan;
- **scan** (a prompt or a chunk): float32, bf16 or predicate results, a
  leading 1 or 2 apart, of two dimensions or more that are all of G, R,
  H, P, N or a chunk's rows (a power of two from 16 to Q), G among them:
  the carried state `[G,R,P,N]`, a chunk's scores `[G,q,q]` (one a chunk
  and layer: they count the chunks), its y `[q,G,R,P]`;
- **gate**: float32 results `[rows, H P]`, `[rows, G]`, `[rows, G, H P /
  G]` (the gate and its grouped norm; `in_proj` and `out_proj` are plain
  matrix products and are not the mixer's own).

A trace with neither a step nor a chunk is one this reading does not
understand: None.
"""

from __future__ import annotations

from collections import defaultdict

from benchmark import trace_reduce
from benchmark.flops import peaks
from benchmark.flops_ssm import state_bytes
from benchmark.moe_ops import result_of

SIZE_KEYS = ("mamba_num_heads", "mamba_head_dim", "ssm_state_size",
             "n_groups", "chunk_size", "hybrid_override_pattern",
             "num_hidden_layers")


def sizes_of(config: dict) -> dict | None:
    if any(k not in config for k in SIZE_KEYS):
        return None
    H, P, N, G = (config[k] for k in SIZE_KEYS[:4])
    pattern = config["hybrid_override_pattern"][:config["num_hidden_layers"]]
    return {"H": H, "P": P, "N": N, "G": G, "Q": config["chunk_size"],
            "C": H * P + 2 * G * N, "L": pattern.count("M"),
            "lanes": config["engine"]["max_batch_size"]}


def device_results(events):
    """(event, dtype, dims of its result) of every operation on the first
    device's `XLA Ops` line, containers left out (their time is their
    bodies'); nothing without a device plane."""
    planes = trace_reduce.device_planes(events or [])
    for e in events or []:
        if not planes or e.plane != planes[0] \
                or e.line != trace_reduce.OPS_LINE \
                or trace_reduce.opcode_of(e.name) in trace_reduce.CONTAINERS:
            continue
        r = result_of(e.name)
        if r is not None:
            yield e, r[0], r[1]


def ssm_ops(events, s: dict, device_kind: str) -> dict | None:
    """{"step": (seconds, state updates), "scan": (seconds, {q: chunks of
    q rows}), "conv": seconds, "gate": seconds} on the first device. A
    state update is one layer of one decode step; a chunk is one pass of
    one layer's chunk loop."""
    H, P, N, G, Q, C, L, lanes = (s[k] for k in "H P N G Q C L lanes".split())
    inner = {G, H // G, H, P, N}
    rows = {q for q in (16, 32, 64, 128, 256, 512) if q <= Q}
    floor_ns = 0.25e9 * state_bytes(lanes, H, P, N) \
        / peaks(device_kind)["hbm_bytes_per_s"]
    step, updates, scan, conv, gate = 0.0, 0, 0.0, 0.0, 0.0
    chunks = defaultdict(int)
    for e, dtype, dims in device_results(events):
        sec = e.dur_ns / 1e9
        body = dims[1:] if dims and dims[0] in (1, 2) else dims
        if C in dims:
            conv += sec
        elif dims == (L, lanes, H, P, N):
            if e.dur_ns >= floor_ns:
                step += sec
                updates += 1
            else:
                scan += sec
        elif dtype == "f32" and len(dims) >= 2 and dims[1:] in (
                (H * P,), (G,), (G, H * P // G)):
            gate += sec
        elif dtype == "f32" and dims[0] == lanes and (
                dims[1:] == (H,) or (3 <= len(dims) <= 4
                                     and set(dims[1:]) <= inner)):
            step += sec
        elif dtype in ("f32", "bf16", "pred") and len(body) >= 2 \
                and set(body) <= inner | rows and G in body:
            scan += sec
            if len(body) == 3 and body[0] == G and body[1] == body[2] \
                    and body[1] in rows:
                chunks[body[1]] += 1
    if not updates and not chunks:
        return None
    return {"step": (step, updates), "scan": (scan, dict(chunks)),
            "conv": conv, "gate": gate}


def from_observed(observed: dict) -> dict | None:
    s = sizes_of(observed["config"])
    if s is None:
        return None
    return ssm_ops(observed.get("events"), s, observed["device_kind"])
