"""CPU tests of the mimo-v2.5 cell's readers (PR 34): the required
operations and bytes against hand-worked numbers, and the four readers on
a synthetic window whose labels are the ones a v5e trace of the cell
carries. Run by hand with the rest of `benchmark/selftest`."""

import importlib.util
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import attn_ops, flops_attn_ctx, flops_moe_held_glu  # noqa: E402
from benchmark.trace_reduce import OPS_LINE, Event  # noqa: E402

KIND = "TPU v5 lite"
DEV = "/device:TPU:0"


def reader(name):
    path = os.path.join(ROOT, "benchmark", "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("m_" + name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def config():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "mimo-v2.5.json")) as f:
        return json.load(f)


def test_the_kinds_of_the_configuration_file():
    full, window = attn_ops.kinds_of(config())
    assert (full["layers"], full["heads"], full["row_bytes"],
            full["window"]) == (2, 4, 2 * (768 + 512), None)
    assert (window["layers"], window["heads"], window["row_bytes"],
            window["window"], window["window_slots"]) == (
                5, 8, 2 * (1536 + 1024), 128, 144)
    assert attn_ops.kinds_of({"hidden_size": 1}) is None


def test_flops_and_bytes_by_hand():
    # one (row, slot) pair: q.k over 192 and p.v over 128, 64 heads
    assert flops_attn_ctx.ctx_flops(1, 64, 192, 128) == 2 * 64 * 320
    assert flops_attn_ctx.ctx_bytes(3000, 2560) == 7_680_000
    # a window kind: the first row sees 127 cached slots, the next 126, ...
    assert flops_attn_ctx.window_pairs(1, 127) == 127
    assert flops_attn_ctx.window_pairs(256, 127) == 127 * 128 / 2
    assert flops_attn_ctx.window_pairs(4, 10) == 10 + 9 + 8 + 7
    cfg = config()
    kinds = attn_ops.kinds_of(cfg)
    # a decode step of 32 lanes at 3,000 slots: 2 x 32 x 3000 x 2560 B of
    # the full kind and 5 x 32 x 127 x 5120 B of the window kind, 0.596 GB:
    # memory, 0.727 ms
    t = flops_attn_ctx.program_least_seconds(
        kinds, {"full": 3000.0, "window": 127.0}, 1, 32, cfg, KIND)
    assert t == pytest.approx((2 * 32 * 3000 * 2560 + 5 * 32 * 127 * 5120)
                              / 819e9, rel=1e-6)
    # a chunk of 256 rows at 1,536 slots: the products bound it
    t = flops_attn_ctx.program_least_seconds(
        kinds, {"full": 1536.0, "window": 127.0}, 256, 1, cfg, KIND)
    assert t == pytest.approx(
        (2 * 256 * 1536 + 5 * 8128) * 2 * 64 * 320 / 197e12, rel=1e-6)
    # an expert here is three matrices of 4096 x 2048: 25,165,824 parameters
    assert flops_moe_held_glu.held_layer_bytes(1, 0, 4096, 2048) \
        == 2 * 25_165_824
    assert flops_moe_held_glu.held_layer_flops(1, 4096, 2048) \
        == 2 * 25_165_824
    # a decode step, 16 pairs on 10 held experts touched: 0.615 ms of reads
    t, bound = flops_moe_held_glu.held_layer_least_seconds(
        16, 10, 32, 4096, 2048, KIND)
    assert bound == "memory" and t == pytest.approx(0.6152e-3, rel=1e-3)


def op(label, start_us, dur_us):
    return Event(DEV, OPS_LINE, label, start_us * 1e3, dur_us * 1e3)


LOOP = ("while.{n} while (s32[], f32[{g},4,16,{t}], f32[{g},4,16,{t}], "
        "f32[{g},{t},4,16,128], ...)")


def window():
    """One decode step's and one 256-row chunk's context reads and one
    expert layer of each, back to back, labelled as the v5e's trace of the
    cell labels them."""
    t, events = 0.0, []

    def add(label, dur):
        nonlocal t
        events.append(op(label, t, dur))
        t += dur

    for layer in range(2):  # decode: 8 groups a full layer, 32 rows first
        for g, dur in ((32, 1), (28, 90), (24, 240), (20, 130), (16, 90),
                       (12, 80), (8, 230), (4, 170)):
            # the loop's event covers its body's operations
            events.append(op(LOOP.format(n=21 + layer, g=g, t=1), t, dur))
            add("reshape.2003 reshape bf16[8,64,4,192]", dur)
    for layer in range(5):  # decode: the window kind's one tile
        add("reshape.158 reshape bf16[32,144,8,192]", 32)
        add("bitcast_reduce_fusion.16 fusion (f32[32,8,8], "
            "bf16[32,8,144,8])", 9)
    add("convolution_bitcast_fusion.1 fusion bf16[16,32,2048]", 430)
    add("fusion.513 fusion bf16[32,4096]", 710)
    add("fusion.766 fusion (f32[32], bf16[32,4096])", 390)  # dense down
    for layer in range(2):  # chunk: one loop a full layer
        events.append(op(LOOP.format(n=9 + layer, g=1, t=256), t, 590))
        add("bitcast_add_fusion.21 fusion f32[1,256,4,16,128]", 590)
    for layer in range(5):
        add("fusion.150 fusion (f32[8,8,256], bf16[8,144,256,8])", 6.5)
    add("fusion.463 fusion bf16[16,2048,256]", 427)
    add("fusion.482 fusion bf16[16,2048,256]", 427)
    add("fusion.88 fusion bf16[256,4096]", 473)
    add("fusion.444 fusion bf16[256,19072]", 230)  # the head: not counted
    return events, t


def observed(events):
    cfg = config()

    def ctx(full_d, full_p, win_d, win_p, launches):
        def counts(valid, calls):
            return {"slots_read": 2 * valid, "slots_valid": valid,
                    "slots_reach": valid, "slots_full": calls * 8704}
        return {"full": {"decode": counts(full_d, 0),
                         "prefill": counts(full_p, launches)},
                "window": {"decode": counts(win_d, 0),
                           "prefill": counts(win_p, launches)}}

    def moe(n):
        return {k: {"pairs": n * p, "held_pairs": n * h,
                    "held_experts_touched": n * tch, "layer_calls": n * 6,
                    "experts_touched": n * 100, "expert_pairs": [0] * 256}
                for k, (p, h, tch) in {
                    "decode": (6 * 256, 6 * 16, 6 * 10),
                    "prefill": (6 * 2048, 6 * 128, 6 * 16)}.items()}

    def stats(n):
        return {"steps": {"decode": n, "prefill": n},
                "context_by_kind": ctx(n * 32 * 3000, n * 1536,
                                       n * 32 * 127, n * 127, n),
                "moe": moe(n),
                "kv": {"full": {"pages_used": 6000, "window": None},
                       "window": {"pages_used": 330, "window": 128}}}

    return {"config": cfg, "device_kind": KIND, "events": events,
            "before": {"stats": stats(0)}, "after": {"stats": stats(100)},
            "polls": [stats(1), stats(2)]}


def test_ctx_ops_finds_the_loops_and_the_window_tiles():
    events, _ = window()
    found = attn_ops.ctx_ops(events, config())
    assert found["loops"][(32, 1)] == (pytest.approx(2e-6), 2)
    assert found["loops"][(1, 256)] == (pytest.approx(1180e-6), 2)
    assert sum(n for _, n in found["loops"].values()) == 18
    assert found["window"] == pytest.approx((5 * 41 + 5 * 6.5) * 1e-6)
    assert attn_ops.ctx_ops([], config()) is None


def test_the_readers_on_a_synthetic_window():
    events, total = window()
    seen = observed(events)
    took = 2 * 1031 + 5 * 41 + 2 * 590 + 5 * 6.5
    assert reader("attn_ctx_share_pct")(seen) == pytest.approx(
        100 * took / total)
    cfg = config()
    kinds = attn_ops.kinds_of(cfg)
    least = flops_attn_ctx.program_least_seconds(
        kinds, {"full": 3000.0, "window": 127.0}, 1, 32, cfg, KIND) \
        + flops_attn_ctx.program_least_seconds(
            kinds, {"full": 1536.0, "window": 127.0}, 256, 1, cfg, KIND)
    got = reader("attn_ctx_roofline_pct")(seen)
    assert got == pytest.approx(100 * least / (took * 1e-6))
    assert 0 < got < 100
    # the held experts: 16 pairs on 10 experts a decode call, 128 on 16 a
    # chunk's
    least = flops_moe_held_glu.held_layer_least_seconds(
        16, 10, 32, 4096, 2048, KIND)[0] \
        + flops_moe_held_glu.held_layer_least_seconds(
            128, 16, 256, 4096, 2048, KIND)[0]
    got = reader("moe_held_glu_roofline_pct")(seen)
    assert got == pytest.approx(
        100 * least / ((430 + 710 + 427 + 427 + 473) * 1e-6))
    assert 0 < got < 100
    assert reader("kv_window_live_share_pct")(seen) == pytest.approx(
        100 * 330 / 6000)


def test_the_readers_find_nothing_at_a_parent_without_the_counters():
    events, _ = window()
    seen = observed(events)
    for edge in ("before", "after"):
        del seen[edge]["stats"]["context_by_kind"]
    seen["polls"] = [{"kv": None}, {}]
    assert reader("attn_ctx_roofline_pct")(seen) is None
    assert reader("kv_window_live_share_pct")(seen) is None
    other = dict(seen, config={"hidden_size": 1280, "engine": {}})
    for name in ("attn_ctx_share_pct", "attn_ctx_roofline_pct",
                 "moe_held_glu_roofline_pct"):
        assert reader(name)(other) is None
    assert reader("attn_ctx_share_pct")(dict(seen, events=None)) is None
