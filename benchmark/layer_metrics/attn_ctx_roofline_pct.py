"""The cached-context read's share of its roofline in the traced window,
the XLA read's (no kernel was written): the least time the chip could take
for the K and V bytes the lanes' lengths and windows require and the score
and value products over them (`benchmark/flops_attn_ctx.py`), over the
device time of the operations that do it (`attn_ops.ctx_ops`).

The trace says which programs ran (a decode program's first tile loop a
full layer has all `max_batch_size` lanes; a chunk's one loop has one
lane of T rows) and how long their reads took; what they had to read is
the window's mean a program, from `engine_stats()["context_by_kind"]`:
visible slots (`slots_valid`) a decode lane and step, and a chunk launch
(launches = d`slots_full` / the slots a table holds). A chunk's rows are
taken as its bucket's T, padding included. Bound by memory in decode and
by the products in a chunk."""
from benchmark import attn_ops, flops_attn_ctx
from benchmark.readers import counter_delta


def read(observed):
    cfg, events = observed["config"], observed.get("events")
    found = attn_ops.ctx_ops(events, cfg) if events else None
    kinds = attn_ops.kinds_of(cfg)
    if not found or not found["loops"] or not kinds:
        return None
    lanes = cfg["engine"]["max_batch_size"]
    page = cfg["engine"]["block_size"]
    table_slots = -(-cfg["engine"]["max_model_len"] // page) * page
    steps = counter_delta(observed, "steps", "decode")
    visible = {"decode": {}, "prefill": {}}
    for k in kinds:
        for program in visible:
            c = flops_attn_ctx.context_counters(observed, k["name"], program)
            if c is None:
                return None
            calls = steps * lanes if program == "decode" \
                else c["slots_full"] / table_slots
            if not calls:
                return None
            visible[program][k["name"]] = c["slots_valid"] / calls
    full_layers = sum(k["layers"] for k in kinds if k["window"] is None)
    took = found["window"] + sum(s for s, _ in found["loops"].values())
    least = 0.0
    for (G, T), (_, loops) in found["loops"].items():
        if T == 1 and G == lanes:  # one such loop a full layer and step
            least += loops / full_layers \
                * flops_attn_ctx.program_least_seconds(
                    kinds, visible["decode"], 1, lanes, cfg,
                    observed["device_kind"])
        elif T > 1:  # one loop a full layer and chunk
            least += loops / full_layers \
                * flops_attn_ctx.program_least_seconds(
                    kinds, visible["prefill"], T, 1, cfg,
                    observed["device_kind"])
    return 100.0 * least / took if took > 0 and least > 0 else None
