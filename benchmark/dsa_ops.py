"""Which device operations of a trace are a latent kind's index pass, its
top-k and its attention core (`ray_tpu/ops/context_attention.py`
`attend_selected`), and the kind's counters in the window.

The program marks the parts with `jax.named_scope` (`attn.index.score`,
`attn.index.topk`, `attn.mla.core`), but the labels `trace_reduce.load`
keeps are `name opcode result`, so the operations are told by what they
return, as `attn_ops.py` tells a full kind's, from the configuration's
sizes (H heads on a latent of R + r lanes, P of padding; `index_topk` K;
lanes B; chunks of T rows):

- the **index pass** is one `while` a group of lanes and layer whose
  carry starts ``(s32[], f32[B, T, S], ...)``, S the slots a padded block
  table holds: the score buffer, all B lanes' in every group's loop;
- the **top-k** is a `conditional` whose result is the choice, ``pred[B,
  T, S + T]`` (its body, the 32-step search for the k-th largest and the
  tie rule, is skipped while no lane has more than K slots): one a layer
  and program, which counts the programs, a decode step's by T = 1, a
  chunk's by B = 1 and T > 1;
- the **core** is one `while` a group of lanes and layer whose carry is
  the running softmax over the latent tiles, ``(s32[], f32[b,1,H,T],
  f32[b,1,H,T], f32[b,T,1,H,R], ...)``, b the lanes the loop's rows
  belong to (1 in a chunk; a decode step's groups, longest first: all B,
  then B less a group, ...): a chunk's by T > 1, a decode step's by T = 1.
  A `while`'s own event covers its body. The start of the softmax on the
  program's own rows is not counted. The experts' width, the query
  latent's rank and K are all 2,048 at GLM-5, so K alone tells nothing.

A configuration without these keys is not this reading's: None.
"""

from __future__ import annotations

import re
from collections import defaultdict

from benchmark import flops_dsa, trace_reduce
from benchmark.readers import counter_delta

SIZE_KEYS = ("kv_lora_rank", "qk_rope_head_dim", "index_topk",
             "index_n_heads", "index_head_dim", "num_attention_heads",
             "num_hidden_layers")
_INDEX_LOOP = re.compile(r"\(s32\[\], f32\[(\d+),(\d+),(\d+)\][,)]")
_CORE_LOOP = re.compile(
    r"\(s32\[\], f32\[(\d+),1,(\d+),(\d+)\], f32\[\1,1,\2,\3\], "
    r"f32\[\1,\3,1,\2,(\d+)\]")
_RESULT = re.compile(r"^\S+ \S+ \(?([a-z0-9]+)\[([0-9,]*)\]")


def counters(observed: dict) -> dict | None:
    """{program: after - before of
    `engine_stats()["context_by_kind"][kind][program]`} for the kind of KV
    layer that selects, inside the window. None where the program has no
    such kind or no such counters (the parent)."""
    if not observed.get("before") or not observed.get("after"):
        return None
    after, before = (observed[k]["stats"] for k in ("after", "before"))
    kind = next((name for name, kv in (after.get("kv") or {}).items()
                 if kv.get("select")), None)
    by = (after.get("context_by_kind") or {}).get(kind)
    if not by or "slots_selected" not in by.get("decode", {}):
        return None
    was = (before.get("context_by_kind") or {}).get(kind) or {}
    return {program: {k: v - (was.get(program) or {}).get(k, 0)
                      for k, v in now.items()}
            for program, now in by.items()}


def latent_ops(events, config: dict) -> dict | None:
    """{"index": {(B, T): (seconds, loops)}, "topk": seconds, "choices":
    {(B, T): conditionals}, "core": {T: (seconds, loops)}} on the first
    device. None without a device plane or the sizes."""
    planes = trace_reduce.device_planes(events or [])
    if any(k not in config for k in SIZE_KEYS) or "engine" not in config \
            or not planes:
        return None
    H, R = config["num_attention_heads"], config["kv_lora_rank"]
    least = config["engine"]["max_model_len"]
    index = defaultdict(lambda: [0.0, 0])
    core = defaultdict(lambda: [0.0, 0])
    choices = defaultdict(int)
    topk = 0.0
    for e in events:
        if e.plane != planes[0] or e.line != trace_reduce.OPS_LINE:
            continue
        opcode = trace_reduce.opcode_of(e.name)
        if opcode == "while":
            m = _CORE_LOOP.search(e.name)
            if m and (int(m.group(2)), int(m.group(4))) == (H, R):
                core[int(m.group(3))][0] += e.dur_ns / 1e9
                core[int(m.group(3))][1] += 1
                continue
            m = _INDEX_LOOP.search(e.name)
            if m and int(m.group(3)) >= least:
                key = (int(m.group(1)), int(m.group(2)))
                index[key][0] += e.dur_ns / 1e9
                index[key][1] += 1
        elif opcode == "conditional":
            m = _RESULT.match(e.name)
            dims = tuple(int(x) for x in m.group(2).split(",")) \
                if m and m.group(2) else ()
            if m and m.group(1) == "pred" and len(dims) == 3 \
                    and dims[2] > least:
                topk += e.dur_ns / 1e9
                choices[dims[:2]] += 1
    return {"index": {k: tuple(v) for k, v in index.items()},
            "topk": topk, "choices": dict(choices),
            "core": {k: tuple(v) for k, v in core.items()}}


def programs(found: dict, config: dict) -> tuple[float, dict]:
    """(decode steps, {rows: chunks}) in the trace: the choices made for
    one row a lane, whatever the lanes (a step's lanes are those that are
    decoding: 16 of a saturated cell's 32 while the others prefill), and
    those for one lane of T > 1 rows (a chunk's bucket), over the
    layers."""
    layers = config["num_hidden_layers"]
    return (sum(n for (b, t), n in found["choices"].items() if t == 1)
            / layers,
            {t: n / layers for (b, t), n in found["choices"].items()
             if b == 1 and t > 1})


def busy_seconds(events) -> float:
    return sum(e - s for s, e in trace_reduce.busy_intervals(
        events, trace_reduce.device_planes(events)[0])) / 1e9


def means(observed: dict) -> dict | None:
    """{"decode": (valid, selected) slots a decode STEP, all its lanes
    together, "prefill": the same a chunk launch} over the window, or
    None."""
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
    return {p: (moved[p]["slots_valid"] / n, moved[p]["slots_selected"] / n)
            for p, n in calls.items()}


def least_seconds(observed: dict, found: dict, part: int) -> float | None:
    """The least seconds the chip could take for the traced programs'
    index passes (`part` 0) or attention cores (1): the programs the
    trace holds (`programs`), each at the window's mean of what a program
    of its kind had to score and attend (`means`; a step's lanes have one
    row each, so its slots count as one lane's)."""
    cfg = observed["config"]
    mean = means(observed)
    if mean is None:
        return None
    steps, chunks = programs(found, cfg)
    kind = observed["device_kind"]
    total = steps * flops_dsa.program_least_seconds(
        cfg, 1, 1, *mean["decode"], kind)[part]
    for rows, n in chunks.items():
        total += n * flops_dsa.program_least_seconds(
            cfg, rows, 1, *mean["prefill"], kind)[part]
    return total
