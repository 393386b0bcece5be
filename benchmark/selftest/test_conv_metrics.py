"""CPU tests of the lfm2 cell's readers (PR 44): the conv operator's
required operations and bytes against hand-worked numbers, the two trace
readers and the counter reader on a synthetic window whose labels are the
ones a v5e trace of the cell carries, and the new names against the
driver's rules. Run by hand with the rest of `benchmark/selftest`."""

import importlib.util
import json
import os
import re
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import conv_ops, flops_conv  # noqa: E402
from benchmark.trace_reduce import OPS_LINE, Event  # noqa: E402

D, K = 2048, 3
KIND = "TPU v5 lite"
DEV = "/device:TPU:0"
CELL = "serve-lfm2-8b-a1b-rag-agent-sat"
NEW = ("conv_share_pct", "conv_roofline_pct", "conv_carried_chunks_pct",
       "decode_lanes_per_step", "decode_padded_rows_pct")


def reader(name):
    path = os.path.join(ROOT, "benchmark", "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("m_" + name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def config():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "lfm2-8b-a1b.json")) as f:
        return json.load(f)


def test_flops_and_bytes_by_hand():
    # the operator's weights: 2048 x 6144 + 2048 x 2048 + 3 x 2048 values,
    # 16,783,360 (ISSUE 44's "16.8 M"), 33.6 MB in bf16
    assert flops_conv.conv_operator_bytes(0, 0, D, K) == 2 * 16_783_360
    # a 256-row chunk: rows in and out 2 x 256 x 2048 x 2 B, one lane's
    # two carried rows read and written
    assert flops_conv.conv_operator_bytes(256, 1, D, K) == \
        33_566_720 + 2_097_152 + 16_384
    # its products 2 x 256 x 2048 x 8192 = 8.59 GFLOP, elementwise 8 a
    # row and channel
    assert flops_conv.conv_operator_flops(256, D, K) == \
        8_589_934_592 + 256 * 2048 * 8
    # 43.6 us of arithmetic against 43.6 us of bytes: the chunk sits at
    # the ridge (35.68 MB / 819 GB/s = 43.56 us; 8.594 GFLOP / 197 T = 43.62)
    t, bound = flops_conv.conv_operator_least_seconds(256, 1, D, K, KIND)
    assert bound == "compute" and t == pytest.approx(43.62e-6, rel=1e-3)
    # a chunk of 128 rows and a 64-lane decode step: the weights' read
    # sets both, 42.3 and 42.9 us
    t, bound = flops_conv.conv_operator_least_seconds(128, 1, D, K, KIND)
    assert bound == "memory" and t == pytest.approx(
        (33_566_720 + 1_048_576 + 16_384) / 819e9, rel=1e-6)
    t, bound = flops_conv.conv_operator_least_seconds(64, 64, D, K, KIND)
    assert bound == "memory" and t == pytest.approx(
        (33_566_720 + 524_288 + 1_048_576) / 819e9, rel=1e-6)


def op(label, start_us, dur_us):
    return Event(DEV, OPS_LINE, label, start_us * 1e3, dur_us * 1e3)


# one conv block and its expert feed-forward, as the compiled programs
# name them (decode of 64 lanes; a chunk of 256 rows), with what the
# scheduler puts between: the weights' asynchronous slices, another
# layer's weight relaid, the norm's `[rows]`
DECODE = [
    ("slice-start.4 slice-start ((bf16[2048,6144]), bf16[512,6144], s32[])",
     1, False),
    ("custom-call.198 custom-call:ConcatBitcast bf16[2048,6144]", 0, False),
    ("fusion.644 fusion bf16[64,6144]", 36, True),
    ("slice_multiply_fusion.15 fusion bf16[64,2048]", 2, True),
    ("fusion.28 fusion bf16[64,2048]", 1, True),
    ("fusion.764 fusion (bf16[18,64,2048], bf16[18,64,2048])", 3, True),
    ("fusion.1259 fusion (f32[1,2048], f32[1,2048], f32[1,2048])", 1, True),
    ("fusion.545 fusion f32[64,2048]", 2, True),
    ("fusion.29 fusion f32[64,2048]", 1, True),
    ("copy.380 copy bf16[2048,2048]", 9, False),
    ("fusion.388 fusion (f32[64], bf16[64,2048])", 12, True),
    ("add_rsqrt_fusion.53 fusion f32[64]", 1, False),
    ("broadcast_add_fusion.20 fusion (f32[64,32], f32[64,32])", 2, False),
    ("convolution_bitcast_fusion.43 fusion bf16[8,64,1792]", 80, False),
    ("fusion.176 fusion bf16[64,2048]", 150, False),
    ("fusion.385 fusion (f32[64], bf16[64,2048])", 2, False),
]
CHUNK = [
    ("fusion.734 fusion bf16[256,6144]", 50, True),
    ("slice_multiply_fusion.16 fusion bf16[256,2048]", 4, True),
    ("pad_maximum_fusion.2 fusion bf16[258,2048]", 2, True),
    # the router's buffer copied ahead: `[rows, E]`, and no computation
    ("copy-start.62 copy-start (s32[256,32], s32[256,32], u32[])", 0, False),
    # the operator waits for the last slices of its own `out_proj`
    ("slice-done.116 async-done bf16[512,2048]", 7, True),
    ("slice-done.23 async-done bf16[512,7168]", 2, False),  # a dense w1's
    ("fusion.474 fusion (f32[256], bf16[256,2048])", 18, True),
    ("broadcast_select_fusion.104 fusion (bf16[1,1,2048], bf16[1,1,2048])",
     1, True),
    ("add_rsqrt_fusion.53 fusion f32[256]", 1, False),
    ("dynamic-update-slice.41 dynamic-update-slice bf16[18,64,2048]", 1,
     True),
    ("fusion.1309 fusion bf16[1,2048]", 1, True),
    ("fusion.1002 fusion bf16[256,7168]", 60, False),  # a dense block's
    ("fusion.465 fusion (f32[256], bf16[256,2048])", 40, False),
]


def window():
    t, events, mine = 0.0, [], {64: 0.0, 256: 0.0}
    for rows, ops in ((64, DECODE), (256, CHUNK), (64, DECODE)):
        for label, dur, conv in ops:
            events.append(op(label, t, dur))
            t += dur + 0.5
            if conv:
                mine[rows] += dur
    # a call the trace cut off: opened, never closed
    events.append(op("fusion.644 fusion bf16[64,6144]", t, 36))
    return events, mine


def observed(events):
    return {"events": events, "config": config(), "device_kind": KIND}


def test_conv_ops_tells_the_operators_operations():
    events, mine = window()
    found = conv_ops.conv_ops(events, conv_ops.sizes_of(config()))
    assert set(found) == {64, 256}
    assert found[64][1] == 2 and found[256][1] == 1
    assert found[64][0] == pytest.approx(mine[64] * 1e-6)
    assert found[256][0] == pytest.approx(mine[256] * 1e-6)


def test_the_two_trace_readers_on_a_synthetic_window():
    events, mine = window()
    obs = observed(events)
    busy = sum(e.dur_ns for e in events) / 1e3
    assert reader("conv_share_pct")(obs) == pytest.approx(
        100 * (mine[64] + mine[256]) / busy)
    # the roofline over the calls the products bound (the 256-row chunk)
    # alone: the 64-row decode calls' least time is their weights' read,
    # which is in none of their events
    least, bound = flops_conv.conv_operator_least_seconds(256, 1, D, K, KIND)
    share = reader("conv_roofline_pct")(obs)
    assert bound == "compute"
    assert share == pytest.approx(100 * least / (mine[256] * 1e-6))
    assert 0 < share < 100
    # a window of decode calls alone has no call to read it from
    decode_only = observed([e for e in events if "[256," not in e.name
                            and "[258," not in e.name])
    assert reader("conv_share_pct")(decode_only) is not None
    assert reader("conv_roofline_pct")(decode_only) is None


def test_the_readers_find_nothing_where_there_is_nothing():
    events, _ = window()
    for name in NEW[:2]:
        # another configuration's trace; no trace; no conv operator in it
        other = observed(events)
        other["config"] = {"hidden_size": 2048, "engine": {}}
        assert reader(name)(other) is None
        assert reader(name)(observed(None)) is None
        assert reader(name)(observed(
            [op("fusion.1 fusion bf16[64,2048]", 0, 5)])) is None
    # a parent without the counter, and a family without state
    stats = {"state": {"resets": 3}}
    assert reader("conv_carried_chunks_pct")(
        {"before": {"stats": stats}, "after": {"stats": stats}}) is None
    assert reader("conv_carried_chunks_pct")(
        {"before": {"stats": {"state": {}}},
         "after": {"stats": {"state": {}}}}) is None
    assert reader("conv_carried_chunks_pct")({}) is None
    for name in NEW[3:]:  # the same, and a window with no decode step
        assert reader(name)({}) is None
        assert reader(name)({"before": {"stats": stats},
                             "after": {"stats": stats}}) is None
        still = {"state": {"decode_lanes": 7, "decode_steps": {"8": 1}}}
        assert reader(name)({"before": {"stats": still},
                             "after": {"stats": still}}) is None


def test_the_counter_reader_takes_the_windows_delta():
    obs = {"before": {"stats": {"state": {"resets": 10, "carried": 90}}},
           "after": {"stats": {"state": {"resets": 50, "carried": 490}}}}
    assert reader("conv_carried_chunks_pct")(obs) == pytest.approx(
        100 * 400 / 440)


def test_the_decode_step_readers_take_the_windows_delta():
    # 100 steps of 32 rows and 4 of 64 in the window (the 16-row program
    # ran before it alone), 2,600 lanes owned
    obs = {"before": {"stats": {"state": {
               "decode_lanes": 400,
               "decode_steps": {"16": 9, "32": 20}}}},
           "after": {"stats": {"state": {
               "decode_lanes": 3000,
               "decode_steps": {"16": 9, "32": 120, "64": 4}}}}}
    assert reader("decode_lanes_per_step")(obs) == pytest.approx(2600 / 104)
    assert reader("decode_padded_rows_pct")(obs) == pytest.approx(
        100 * (3456 - 2600) / 3456)


def test_the_new_names_resolve_and_obey_the_rules():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    name = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
    assert len(CELL) == 31 and name.match(CELL)
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "lfm2-8b-a1b", "rag-agent-sat", 1)
    entry = next(c for c in bench["configs"] if c["name"] == "lfm2-8b-a1b")
    assert entry["reduced"] == ["num_experts", "vocab_size",
                                "max_position_embeddings"]
    assert entry["file"] == "benchmark/configs/lfm2-8b-a1b.json"
    for path in (entry["file"], "benchmark/traffic/rag-agent-sat.json",
                 "benchmark/reference_lfm2.py", "benchmark/parity_lfm2.py",
                 "benchmark/conv_ops.py", "benchmark/flops_conv.py"):
        assert os.path.exists(os.path.join(ROOT, path)), path
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for metric in NEW:
        m = by_name[metric]
        assert name.match(metric) and m["workloads"] == [CELL]
        assert m["moves"] == "serve_tokens_per_s"
        assert m["unit"] == ("lanes" if metric.endswith("_step") else "%")
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "layer_metrics", metric + ".py"))
    # the traffic is ISSUE 44's, letter for letter
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           "rag-agent-sat.json")) as f:
        traffic = json.load(f)
    assert (traffic["loop"], traffic["clients"], traffic["cycle_requests"],
            traffic["preroll_s"]) == ("closed", 128, 128, 12.0)
    assert traffic["prompt_len"] == {"dist": "lognormal", "median": 2048,
                                     "sigma": 0.8, "min": 256, "max": 8192}
    assert traffic["output_len"] == {"dist": "uniform", "min": 128,
                                     "max": 384}
    conf = config()
    assert conf["engine"]["max_batch_size"] == 64
    assert conf["deployment"]["max_ongoing_requests"] == 128
    # every number of the catalog row's config under the same key, but
    # what `reduced` lists
    for key, value in conf["published"].items():
        if key not in entry["reduced"]:
            assert conf[key] == value, key
