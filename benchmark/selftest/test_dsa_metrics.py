"""CPU tests of the glm-5 cell's readers (PR 40): the required operations
and bytes against hand-worked numbers, and the five readers on a
synthetic window whose labels are the ones the cell's programs carry
(compiled for the v5e: the index pass's and the chunk core's `while`, the
choice's `conditional`, a decode step's core loops a group of lanes, as
the v5e's trace of the cell labels them). Run by
hand with the rest of `benchmark/selftest`."""

import importlib.util
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import (  # noqa: E402
    dsa_ops,
    flops_dsa,
    flops_moe_held,
    flops_moe_held_glu,
    moe_stack_ops,
)
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
    with open(os.path.join(ROOT, "benchmark", "configs", "glm-5.json")) as f:
        return json.load(f)


def test_flops_and_bytes_by_hand():
    # one (row, slot) pair of the index pass: 32 heads over 128 lanes
    assert flops_dsa.index_flops(1, 32, 128) == 2 * 32 * 128
    assert flops_dsa.index_bytes(1000, 128) == 256_000
    # one (row, attended slot) pair: 64 heads, q.k over 576, p.v over 512
    assert flops_dsa.core_flops(1, 64, 512, 64) == 2 * 64 * (576 + 512)
    assert flops_dsa.core_bytes(2048, 512, 64) == 2048 * 1152
    cfg = config()
    # a decode step of 32 lanes at 6,000 slots, 2,048 attended: both parts
    # are bound by memory: 5 x 32 x 6000 x 256 B and 5 x 32 x 2048 x 1152 B
    index, core = flops_dsa.program_least_seconds(cfg, 1, 32, 6000.0,
                                                  2048.0, KIND)
    assert index == pytest.approx(5 * 32 * 6000 * 256 / 819e9, rel=1e-6)
    assert core == pytest.approx(5 * 32 * 2048 * 1152 / 819e9, rel=1e-6)
    # a chunk of 256 rows at 4,096 slots: both bound by the products
    index, core = flops_dsa.program_least_seconds(cfg, 256, 1, 4096.0,
                                                  2048.0, KIND)
    assert index == pytest.approx(
        5 * 256 * 4096 * 2 * 32 * 128 / 197e12, rel=1e-6)
    assert core == pytest.approx(
        5 * 256 * 2048 * 2 * 64 * 1088 / 197e12, rel=1e-6)


def op(label, start_us, dur_us):
    return Event(DEV, OPS_LINE, label, start_us * 1e3, dur_us * 1e3)


def window():
    """One decode step's and one 256-row chunk's attention in all five
    layers, with an expert layer's and the output projection's operations
    beside them, labelled as the compiled programs label them."""
    t, events = 0.0, []

    def add(label, dur, inside=None):
        nonlocal t
        if inside:  # a container's event covers its body's operations
            events.append(op(inside, t, dur))
        events.append(op(label, t, dur))
        t += dur

    for layer in range(5):  # decode, 32 lanes
        for group in range(8):
            add("fusion.11 fusion f32[32,1,32,1024]", 14,
                inside=f"while.{71 + group} while (s32[], f32[32,1,17408], "
                "s32[], s32[32,1088], ...)")
        add("fusion.12 fusion u32[32,1,1]", 280,
            inside="conditional.1 conditional (pred[32,1,17409])")
        for group in range(8):  # the core: the groups' loops, longest first
            b = 32 - 4 * group
            add(f"fusion.51 fusion f32[{b},1,1,64,512]", 70,
                inside=f"while.{12 + group} while (s32[], f32[{b},1,64,1], "
                f"f32[{b},1,64,1], f32[{b},1,1,64,512], ...)")
        add("copy.902 copy bf16[16384,2048]", 200)  # wq_b: not counted
        add("fusion.50 fusion bf16[32,6144]", 250)  # wo: not counted
        add("fusion.60 fusion bf16[8,32,2048]", 400)  # experts: not counted
        add("fusion.61 fusion bf16[32,2048]", 50)  # c_q / shared: no
    for layer in range(5):  # a chunk of 256 rows
        add("fusion.70 fusion f32[1,256,32,1024]", 300,
            inside="while.44 while (s32[], f32[1,256,17408], s32[], "
            "s32[1,1088], ...)")
        add("fusion.71 fusion u32[1,256,1]", 540,
            inside="conditional conditional (pred[1,256,17664])")
        add("fusion.72 fusion f32[1,256,1,64,512]", 1200,
            inside="while.45 while (s32[], f32[1,1,64,256], "
            "f32[1,1,64,256], f32[1,256,1,64,512], ...)")
        add("fusion.80 fusion bf16[256,6144]", 600)
        add("fusion.81 fusion bf16[1,256,2048]", 70)
    return events, t


def observed(events):
    cfg = config()

    def stats(n):
        def counts(valid, chosen, calls, read):
            return {"slots_read": n * read, "slots_valid": n * valid,
                    "slots_reach": n * valid,
                    "slots_full": n * calls * 16768,
                    "slots_scored": n * (valid + 1000),
                    "slots_selected": n * chosen}
        return {"steps": {"decode": n, "prefill": n},
                "context_by_kind": {"latent": {
                    "decode": counts(32 * 6000, 32 * 2048, 32, 32 * 2048),
                    "prefill": counts(4096, 2048, 1, 4096),
                    "verify": counts(0, 0, 0, 0)}},
                "kv": {"latent": {"pages_used": 9000, "window": None,
                                  "select": 2048}}}

    return {"config": cfg, "device_kind": KIND, "events": events,
            "before": {"stats": stats(0)}, "after": {"stats": stats(100)},
            "polls": [stats(1), stats(2)]}


def test_latent_ops_tells_the_parts_apart():
    events, _ = window()
    found = dsa_ops.latent_ops(events, config())
    assert found["index"][(32, 1)] == (pytest.approx(5 * 8 * 14e-6), 40)
    assert found["index"][(1, 256)] == (pytest.approx(5 * 300e-6), 5)
    assert found["choices"] == {(32, 1): 5, (1, 256): 5}
    assert found["topk"] == pytest.approx(5 * (280 + 540) * 1e-6)
    assert found["core"] == {256: (pytest.approx(5 * 1200e-6), 5),
                             1: (pytest.approx(5 * 8 * 70e-6), 40)}
    assert dsa_ops.programs(found, config()) == (1.0, {256: 1.0})
    assert dsa_ops.latent_ops([], config()) is None


def test_the_readers_on_a_synthetic_window():
    events, total = window()
    seen = observed(events)
    assert reader("dsa_selected_share_pct")(seen) == pytest.approx(
        100 * (32 * 2048 + 2048) / (32 * 6000 + 4096))
    index_took = 5 * (8 * 14 + 280 + 300 + 540)
    core_took = 5 * (8 * 70 + 1200)
    assert reader("dsa_index_share_pct")(seen) == pytest.approx(
        100 * index_took / total)
    assert reader("mla_attn_share_pct")(seen) == pytest.approx(
        100 * core_took / total)
    cfg = config()
    step = flops_dsa.program_least_seconds(cfg, 1, 1, 32 * 6000.0,
                                           32 * 2048.0, KIND)
    chunk = flops_dsa.program_least_seconds(cfg, 256, 1, 4096.0, 2048.0,
                                            KIND)
    got = reader("dsa_index_roofline_pct")(seen)
    assert got == pytest.approx(100 * (step[0] + chunk[0])
                                / (index_took * 1e-6))
    assert 0 < got < 100
    got = reader("mla_attn_roofline_pct")(seen)
    assert got == pytest.approx(100 * (step[1] + chunk[1])
                                / (core_took * 1e-6))
    assert 0 < got < 100


def test_the_readers_find_nothing_at_a_parent_without_the_kind():
    events, _ = window()
    seen = observed(events)
    for edge in ("before", "after"):
        del seen[edge]["stats"]["context_by_kind"]
        seen[edge]["stats"]["kv"] = {"full": {"window": None}}
    for name in ("dsa_selected_share_pct", "dsa_index_roofline_pct",
                 "mla_attn_roofline_pct"):
        assert reader(name)(seen) is None
    other = dict(observed(events), config={"hidden_size": 1280,
                                           "engine": {}})
    for name in ("dsa_index_share_pct", "dsa_index_roofline_pct",
                 "mla_attn_share_pct", "mla_attn_roofline_pct"):
        assert reader(name)(other) is None
        assert reader(name)(dict(observed(events), events=None)) is None


def test_the_held_expert_readers_would_misread_this_configuration():
    """Why `moe_held_share_pct` and `moe_held_glu_roofline_pct` do not
    list the cell: `held_expert_ops` takes any `bf16[rows, hidden]` result
    that lasts longer than a quarter of the held experts' weights take to
    read (123 us here: 8 experts of two 6144 x 2048 matrices) for the
    experts' weighted sum, and this model's attention output projection,
    201 MB of `wo` read in 245 us at a decode step, is one such: it would
    be counted as a layer call of the experts."""
    cfg = config()
    events = [op("fusion.50 fusion bf16[32,6144]", 0, 250)]  # wo alone
    found = flops_moe_held.held_expert_ops(
        events, cfg["n_routed_experts"], cfg["moe_intermediate_size"],
        cfg["hidden_size"], KIND)
    assert found == {32: (pytest.approx(250e-6), 1)}


def expert_window():
    """Two expert layers of a 16-lane decode step and two of a 256-row
    chunk between their attentions' output projections, labelled and
    ordered as the v5e's trace of the cell has them (`wo` as large as the
    experts' down projections; the shared expert's down projection
    scheduled behind the stacked products once, before them once)."""
    t, events = 0.0, []

    def add(label, dur):
        nonlocal t
        events.append(op(label, t, dur))
        t += dur

    for _ in range(2):  # decode: up stacked, gate + down + sum fused
        add("fusion.492 fusion (f32[16], bf16[16,6144])", 268)  # wo
        add("fusion.470 fusion bf16[16,2048]", 30)  # shared gate * up
        add("convolution_bitcast_fusion.6 fusion bf16[8,16,2048]", 267)
        add("fusion.520 fusion (f32[16], bf16[16,6144])", 31)  # shared down
        add("fusion.391 fusion bf16[16,6144]", 533)
    for _ in range(2):  # a chunk: gate and up stacked, down + sum fused
        add("fusion.320 fusion (f32[256], bf16[256,6144])", 291)  # wo
        add("fusion.323 fusion (f32[256], bf16[256,6144])", 36)  # shared
        add("fusion.362 fusion bf16[8,2048,256]", 311)
        add("fusion.360 fusion bf16[8,2048,256]", 311)
        add("fusion.191 fusion bf16[256,6144]", 361)
    add("fusion.317 fusion (f32[256], bf16[256,6144])", 218)  # dense down
    # a call the trace cut off: stacked products and nothing behind them
    add("fusion.362 fusion bf16[8,2048,256]", 311)
    return events, t


def test_the_stack_reader_tells_the_experts_from_the_output_projection():
    cfg = config()
    events, total = expert_window()
    found = moe_stack_ops.held_stack_ops(
        events, cfg["n_routed_experts"], cfg["moe_intermediate_size"],
        cfg["hidden_size"])
    assert found == {16: (pytest.approx(2 * (267 + 533) * 1e-6), 2),
                     256: (pytest.approx(2 * (311 + 311 + 361) * 1e-6), 2)}
    assert moe_stack_ops.held_stack_ops([], 8, 2048, 6144) is None
    # the output projection alone, which `held_expert_ops` misreads: nothing
    assert moe_stack_ops.held_stack_ops(
        [op("fusion.50 fusion bf16[32,6144]", 0, 250)], 8, 2048, 6144) is None

    def stats(n):
        def moe(rows, touched):  # 4 expert layers a step
            return {"pairs": n * 4 * rows * 8, "held_pairs": n * rows,
                    "held_experts_touched": n * 4 * touched,
                    "experts_touched": n * 4 * 8, "layer_calls": n * 4,
                    "expert_pairs": [n * rows] * 256}
        return {"moe": {"decode": moe(16, 3.5), "prefill": moe(256, 8)}}

    seen = {"config": cfg, "device_kind": KIND, "events": events,
            "before": {"stats": stats(0)}, "after": {"stats": stats(50)}}
    took = 2 * (267 + 533) + 2 * (311 + 311 + 361)
    assert reader("moe_held_stack_share_pct")(seen) == pytest.approx(
        100 * took / total)
    # a thirty-second of a row's 8 pairs land here: 4 pairs on 3.5 experts
    # a decode call, 64 pairs on all 8 a chunk's, both bound by the bytes
    step = flops_moe_held_glu.held_layer_least_seconds(
        4, 3.5, 16, 6144, 2048, KIND)[0]
    chunk = flops_moe_held_glu.held_layer_least_seconds(
        64, 8, 256, 6144, 2048, KIND)[0]
    assert step == pytest.approx(
        (3.5 * 3 * 6144 * 2048 * 2 + 2 * 16 * 6144 * 2) / 819e9, rel=1e-6)
    got = reader("moe_held_stack_roofline_pct")(seen)
    assert got == pytest.approx(100 * 2 * (step + chunk) / (took * 1e-6))
    assert 0 < got < 100
    # a parent without the routing account, another configuration: nothing
    assert reader("moe_held_stack_roofline_pct")(
        dict(seen, after={"stats": {}}, before={"stats": {}})) is None
    other = dict(seen, config={"hidden_size": 1280, "engine": {}})
    for name in ("moe_held_stack_share_pct", "moe_held_stack_roofline_pct"):
        assert reader(name)(other) is None
        assert reader(name)(dict(seen, events=None)) is None
