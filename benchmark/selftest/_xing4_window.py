"""A synthetic traced window of the xing4 cell, for its readers' CPU
tests: one decode step of 32 rows and two chunks of 256 in all six layers,
labelled as the programs compiled for the v5e label them (the AOT compile,
PR 51), and the engine's counters at the window's two edges."""

import importlib.util
import json
import os

from benchmark.trace_reduce import OPS_LINE, Event

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
KIND = "TPU v5 lite"
DEV = "/device:TPU:0"
LAYERS = 6
STEP_LANES, STEP_SLOTS = 24, 24 * 9000  # the window's mean decode step
CHUNK_ROWS, CHUNK_START = 250, 4096  # and its mean chunk launch


def reader(name):
    """`benchmark/layer_metrics/<name>.py`'s `read`, loaded as run.py loads
    it."""
    path = os.path.join(ROOT, "benchmark", "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("m_" + name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def config():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "xing4.0-29b-a4b.json")) as f:
        return json.load(f)


def op(label, start_us, dur_us):
    return Event(DEV, OPS_LINE, label, start_us * 1e3, dur_us * 1e3)


def window(read_us=(60, 1500), maps_us=(3, 9), product_us=(8, 40)):
    """-> (events, the window's microseconds, what was spent on the read,
    on the maps' kernel and on the product with phi)."""
    t, events = 0.0, []
    spent = {"read": 0.0, "kernel": 0.0, "product": 0.0}

    def add(label, dur, inside=None, what=None):
        nonlocal t
        if inside:  # a container's event covers its body's operations
            events.append(op(inside, t, dur))
        events.append(op(label, t, dur))
        t += dur
        if what:
            spent[what] += dur

    def half_layer(rows, which):
        add(f"fusion.{300 + which} fusion f32[{rows}]", 2)  # mean square
        add(f"fusion.9 fusion f32[24,{rows}]", product_us[which],
            what="product")
        add("maximum_bitcast_fusion.3 fusion f32[24,1]", 1, what="product")
        add(f"mhc_maps.{4 + which} custom-call:tpu_custom_call "
            f"f32[24,{rows}]", maps_us[which], what="kernel")
        # the pre-mix, fused with the half's norm: like any projection's
        add(f"fusion.21 fusion (bf16[{rows},3584], bf16[{rows},3584])", 6)

    for layer in range(LAYERS):  # a decode step of 32 rows, groups of 4
        half_layer(32, 0)
        for group in range(8):
            b = 32 - 4 * group
            add(f"fusion.51 fusion f32[{b},1,1,32,512]", read_us[0],
                inside=f"while.{12 + group} while (s32[], f32[{b},1,32,1], "
                f"f32[{b},1,32,1], f32[{b},1,1,32,512], ...)", what="read")
        # wo with the post-mix in its epilogue: the streams, not counted
        add("fusion.50 fusion (bf16[32,3584], bf16[32,3584], "
            "bf16[32,3584])", 40)
        half_layer(32, 0)
        add("fusion.60 fusion bf16[16,32,1024]", 300)  # experts
    for chunk in range(2):
        for layer in range(LAYERS):
            half_layer(256, 1)
            add("fusion.72 fusion f32[1,256,1,32,512]", read_us[1],
                inside="while.45 while (s32[], f32[1,1,32,256], "
                "f32[1,1,32,256], f32[1,256,1,32,512], ...)", what="read")
            add("fusion.80 fusion (bf16[256,3584], bf16[256,3584], "
                "bf16[256,3584])", 300)
            half_layer(256, 1)
            add("fusion.81 fusion bf16[16,256,1024]", 900)
    return events, t, spent


def observed(events, counters=True):
    cfg = config()
    table = -(-cfg["engine"]["max_model_len"] // 16) * 16

    def stats(steps, chunks):
        decode = {"slots_read": steps * 32 * 10240,
                  "slots_valid": steps * STEP_SLOTS,
                  "slots_reach": steps * STEP_SLOTS,
                  "slots_full": steps * 32 * table}
        prefill = {"slots_read": chunks * 4096,
                   "slots_valid": chunks * CHUNK_START,
                   "slots_reach": chunks * CHUNK_START,
                   "slots_full": chunks * table}
        if counters:
            decode.update(rows=steps * STEP_LANES,
                          row_slots=steps * STEP_SLOTS)
            prefill.update(rows=chunks * CHUNK_ROWS,
                           row_slots=chunks * CHUNK_ROWS * CHUNK_START)
        by = {"decode": decode, "prefill": prefill}
        return {"stats": {
            "steps": {"decode": steps, "prefill": chunks},
            "context": by, "context_by_kind": {"latent": by},
            "kv": {"latent": {"latent": True, "select": None}}}}

    return {"events": events, "config": cfg, "device_kind": KIND,
            "before": stats(10, 20), "after": stats(410, 1220)}
