"""Which device operations of a trace are a KDA mixer's.

The program marks the mixer's parts with `jax.named_scope` (`kda.in_proj`,
`kda.conv`, `kda.gate`, `kda.chunk`, `kda.step`, `kda.out`), but the labels
`trace_reduce.load` keeps are `name opcode result` and XLA names a fusion
`fusion.N` whatever its scope. So the operations are told by what they
return, as `ssm_ops.py` tells a Mamba-2 mixer's, from the configuration's
sizes (H heads of d, a convolution over C = 3 H d channels, `lanes` slots,
L layers with state, blocks of Q rows in sub-blocks of 16; the labels are
those the v5e compiler gives the cell's decode-64 and chunk-256 programs:
the AOT compile, PR 61):

- **conv**: any result with a dimension of C (the window's parts
  `bf16[L, lanes, C]`, a chunk's `f32[rows, C]`, a step's `bf16[lanes, 4,
  C]`);
- **gate**: float32 results `[rows, H d]`;
- **step** (decode): the in-place update of every slot's state, a result
  `f32[L, lanes, H, d, d]`; the two sums over the state, one fusion of
  two results `(f32[lanes, H, d], f32[lanes, H, d])` that reads the
  buffer where it lies; and the float32 operations over `lanes` rows that
  put the lanes' inputs in slot order and bring o back (`[lanes, H]`, and
  three or four dimensions of lanes, H, d, 1, 2 with H and d among
  them). By the scopes in the compiled programs' metadata every such
  operation is under `attn.kda` but ONE: the latent layer's rotation
  returns its cosines and sines as `(f32[lanes, 32], f32[lanes, 32])`,
  the rotary half being H wide here, 0.2 us of a step's 3,700 (my chip
  run, PR 61); its rotated key `[lanes, 1, 32, 1]` has no d and is left
  out. A prompt's or a chunk's
  program writes ONE lane's state into the same buffer, and that write
  returns the same shape: it is told apart by its time, which is under a
  quarter of what reading and writing `lanes` slots' state takes at the
  HBM peak (a step cannot be), counted with the chunk, and counts the
  chunk programs' layers;
- **chunk** (a prompt or a chunk): float32 results, leading 1s apart, of
  three dimensions or more that are all of H, d, 2 d, Q, 16, 4, 2 or 1
  with H among them, and that lead with the blocks (8 at most) or with H
  (a block's decayed products `[nb, H, Q, Q]`, the solve's custom call
  `[nb, H, 1, Q, Q]`, the rows `[nb, H, Q, d]`, the scan's `[H, Q, d]`)
  or with a program's 128 or 256 rows over H and d, both (`[rows, 1, H,
  d]`: the latent layer's rotated lanes `[rows, H, 64]` and `[1, rows,
  32, 1]` are not), and the scan's carried state `[H, d, d]`.

The mixer's two projections are plain matrix products and are not the
mixer's own. A trace with neither a step nor a chunk's write is one this
reading does not understand, or another family's: None.
"""

from __future__ import annotations

from benchmark.flops import peaks
from benchmark.flops_kda import state_bytes
from benchmark.ssm_ops import device_results

SIZE_KEYS = ("short_conv_kernel_size", "kda_lower_bound", "head_dim",
             "num_attention_heads", "layer_types", "num_hidden_layers")
BLOCK, SUB = 64, 16  # `ray_tpu/models/kda.py`'s, which are not configuration


def sizes_of(config: dict) -> dict | None:
    if any(k not in config for k in SIZE_KEYS) or "engine" not in config:
        return None
    H, d = config["num_attention_heads"], config["head_dim"]
    kinds = config["layer_types"][:config["num_hidden_layers"]]
    return {"H": H, "d": d, "C": 3 * H * d, "L": kinds.count("kda"),
            "lanes": config["engine"]["max_batch_size"]}


def kda_ops(events, s: dict, device_kind: str) -> dict | None:
    """{"step": (seconds, state updates), "chunk": (seconds, one-lane
    state writes), "conv": seconds, "gate": seconds} on the first device.
    A state update is one layer of one decode step; a one-lane write one
    layer of one prompt's or chunk's program."""
    H, d, C, L, lanes = (s[k] for k in "H d C L lanes".split())
    inner = {H, d, 2 * d, BLOCK, SUB, BLOCK // SUB, 2, 1}
    floor_ns = 0.25e9 * state_bytes(lanes, H, d) \
        / peaks(device_kind)["hbm_bytes_per_s"]
    step = chunk = conv = gate = 0.0
    updates = writes = 0
    for e, dtype, dims in device_results(events):
        sec = e.dur_ns / 1e9
        body = tuple(x for i, x in enumerate(dims)
                     if x != 1 or any(y != 1 for y in dims[:i]))
        if C in dims:
            conv += sec
        elif dims == (L, lanes, H, d, d):
            if e.dur_ns >= floor_ns:
                step += sec
                updates += 1
            else:
                chunk += sec
                writes += 1
        elif dtype != "f32":
            continue
        elif dims == (H, d, d):
            chunk += sec
        elif len(dims) == 2 and dims[1] == H * d:
            gate += sec
        elif dims[:1] == (lanes,) and (dims[1:] == (H,) or (
                3 <= len(dims) <= 4 and {H, d} <= set(dims[1:])
                and set(dims[1:]) <= {H, d, 1, 2})):
            step += sec
        elif len(body) >= 3 and H in body and set(body) <= inner and (
                body[0] in (1, 2, 4, 8, H)  # blocks first, or the scan's
                or (body[0] in (d, 2 * d) and {H, d} <= set(body[1:])
                    and set(body[1:]) <= {1, H, d})):
            chunk += sec
    if not updates and not writes:
        return None
    return {"step": (step, updates), "chunk": (chunk, writes), "conv": conv,
            "gate": gate}


def from_observed(observed: dict) -> dict | None:
    s = sizes_of(observed.get("config") or {})
    if s is None or not observed.get("events"):
        return None
    return kda_ops(observed["events"], s, observed["device_kind"])


def prefill_rows_a_program(observed: dict) -> float | None:
    """Mean real rows of the window's prompt and chunk programs, from the
    engine's counters: the rows they ran (`context.prefill.rows`) over the
    programs that started a slot fresh or from a carried state
    (`state.resets` + `state.carried`)."""
    if not observed.get("before") or not observed.get("after"):
        return None
    moved = []
    for path in (("context", "prefill", "rows"), ("state", "resets"),
                 ("state", "carried")):
        ends = []
        for edge in ("after", "before"):
            found = observed[edge].get("stats") or {}
            for key in path:
                found = found.get(key) if isinstance(found, dict) else None
            ends.append(found)
        if None in ends:
            return None
        moved.append(ends[0] - ends[1])
    rows, programs = moved[0], moved[1] + moved[2]
    return rows / programs if programs > 0 and rows > 0 else None
