"""CPU tests of the ling-3.0-flash-vl cell's readers (PR 61): the required
operations and bytes against hand-worked numbers, `kda_ops.py` on the
labels the v5e compiler gives the cell's decode-64 and chunk-256 programs
(the AOT compile, PR 61), the three readers on a synthetic window, and
every new reader on another cell's trace and stats, where it must say
nothing. Run by hand with the rest of `benchmark/selftest`, and by
`tests/test_benchmark_selftests.py`."""

import importlib.util
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import flops_kda, kda_ops  # noqa: E402
from benchmark.trace_reduce import OPS_LINE, Event  # noqa: E402

H, D, L, LANES = 32, 128, 6, 64
KIND = "TPU v5 lite"
DEV = "/device:TPU:0"
READERS = ("kda_share_pct", "kda_step_roofline_pct",
           "kda_chunk_roofline_pct")


def reader(name):
    path = os.path.join(ROOT, "benchmark", "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("m_" + name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def config(name="ling-3.0-flash-vl"):
    with open(os.path.join(ROOT, "benchmark", "configs",
                           name + ".json")) as f:
        return json.load(f)


def test_the_sizes_come_off_the_configuration_file():
    assert kda_ops.sizes_of(config()) == {
        "H": H, "d": D, "C": 12288, "L": L, "lanes": LANES}
    for other in ("granite-4.0-h-small", "xing4.0-29b-a4b", "gpt2-large"):
        assert kda_ops.sizes_of(config(other)) is None


def test_flops_and_bytes_by_hand():
    # a lane's S of one layer: 32 x 128 x 128 float32, read and written
    assert flops_kda.state_bytes(1, H, D) == 2 * 2_097_152
    # 64 lanes: 268 MB a layer, 1.61 GB over 6 layers a step
    assert flops_kda.state_bytes(LANES, H, D) == 268_435_456
    # a row's q, k, v, g, o (128 each) and beta, float32, 32 heads
    assert flops_kda.rows_bytes(1, H, D) == 4 * 32 * 641
    assert flops_kda.step_flops(LANES, H, D) == 64 * 7 * 32 * 16384
    t, bound = flops_kda.step_least_seconds(LANES, H, D, KIND)
    assert bound == "memory"
    assert t == pytest.approx((268_435_456 + 64 * 82_048) / 819e9)
    # a chunk of 256 rows: 4 blocks of 64, a head 8 x 64^2 x 128 + 6 x 64
    # x 128^2 = 10.5 MFLOP a block, 1.34 GFLOP a layer, 6.8 us at the bf16
    # peak; the rows' float32 inputs and outputs (21.0 MB) and the lane's
    # state (4.2 MB) take 30.8 us at the HBM peak: memory
    assert flops_kda.chunk_flops(256, 64, H, D) == 4 * 32 * (
        8 * 4096 * 128 + 6 * 64 * 16384) == 1_342_177_280
    t, bound = flops_kda.chunk_least_seconds(256, 64, H, D, KIND)
    assert bound == "memory" and t == pytest.approx(
        (4_194_304 + 256 * 82_048) / 819e9)
    assert 1_342_177_280 / 197e12 < t


# labels as the v5e compiler gives them (name opcode result), a layer of a
# decode step of 64 lanes and a layer of a chunk of 256 rows, with the
# nanoseconds this test gives each
DECODE_LAYER = [
    ("fusion.8 fusion bf16[64,12288]", 3_000, "conv"),
    ("select_dynamic-update-slice_fusion.11 fusion (bf16[6,64,12288], "
     "bf16[6,64,12288], bf16[6,64,12288], bf16[64,1,12288])", 9_000, "conv"),
    ("fusion.101 fusion f32[12288,64]", 6_000, "conv"),
    ("fusion.263 fusion (f32[64,32,128], f32[64,32,128])", 2_000, "step"),
    ("divide_multiply_fusion.5 fusion f32[64,32,128]", 2_000, "step"),
    ("fusion.569 fusion (f32[64,32], f32[64,32])", 1_000, "step"),
    ("fusion.9 fusion (f32[64,32,128], f32[64,32,128])", 200_000, "step"),
    ("fusion.262 fusion (f32[64,32,1,128], f32[64,32,1,128])", 2_000,
     "step"),
    ("fusion.700 fusion f32[6,64,32,128,128]", 420_000, "update"),
    # not the mixer's own: the projections, the latent layer, the experts
    ("convolution.5 convolution f32[64,16448]", 60_000, None),
    ("fusion.90 fusion bf16[64,4096]", 2_000, None),
    ("fusion.44 fusion f32[64,32,64]", 2_000, None),
    # the latent layer's rotated key, one lane of 64 a row in pairs
    ("fusion.679 fusion (f32[64,1,32,1], f32[64,1,32,1])", 1_000, None),
    ("ctx_read_paged.1 custom-call:tpu_custom_call bf16[64,32,512]",
     50_000, None),
    ("fusion.234 fusion bf16[16,64,768]", 70_000, None),
]
CHUNK_LAYER = [
    ("fusion.300 fusion bf16[259,12288]", 10_000, "conv"),
    ("fusion.301 fusion f32[256,12288]", 20_000, "conv"),
    ("fusion.302 fusion f32[256,4096]", 5_000, "gate"),
    ("fusion.303 fusion (f32[256,1,32,128], f32[256,1,32,128], "
     "f32[256,1,32,128])", 9_000, "chunk"),
    ("fusion.304 fusion (f32[4,32,4,16,16], f32[4,32,4,16,16])", 40_000,
     "chunk"),
    ("fusion.305 fusion f32[4,32,4,16,64]", 20_000, "chunk"),
    ("triangular_solve.1 custom-call f32[4,32,1,64,64]", 60_000, "chunk"),
    ("fusion.306 fusion f32[4,32,64,256]", 30_000, "chunk"),
    ("fusion.307 fusion f32[32,64,128]", 12_000, "chunk"),
    ("fusion.308 fusion f32[32,128,128]", 12_000, "chunk"),
    ("fusion.309 fusion f32[6,64,32,128,128]", 8_000, "write"),
    ("while.3 while (s32[], f32[32,128,128], f32[4,32,64,128])", 500_000,
     None),  # a container: its time is its body's
    ("convolution.9 convolution f32[256,16448]", 200_000, None),
    ("fusion.400 fusion f32[1,32,256,1024]", 90_000, None),
    ("fusion.402 fusion f32[1,256,32,64]", 3_000, None),
    ("fusion.1023 fusion (f32[1,256,32,1], f32[1,256,32,1])", 1_000, None),
    ("fusion.401 fusion bf16[16,256,768]", 150_000, None),
]


def _events(steps=3, chunks=2):
    events, at = [], 1_000.0
    for ops, n in ((DECODE_LAYER, steps * L), (CHUNK_LAYER, chunks * L)):
        for _ in range(n):
            for label, dur, _ in ops:
                events.append(Event(DEV, OPS_LINE, label, at, float(dur)))
                at += dur + 10
    return events


def _observed(events, steps=3, chunks=2, lanes=48, rows=200):
    def stats(k):
        return {"stats": {
            "state": {"decode_lanes": k * steps * lanes,
                      "decode_steps": {"64": k * steps},
                      "resets": k * 1, "carried": k * (chunks - 1)},
            "context": {"prefill": {"rows": k * chunks * rows}}}}

    return {"config": config(), "device_kind": KIND, "events": events,
            "before": stats(1), "after": stats(2)}


def _sum(ops, *kinds):
    return sum(dur for _, dur, kind in ops if kind in kinds) / 1e9


def test_the_operations_are_told_by_what_they_return():
    found = kda_ops.kda_ops(_events(), kda_ops.sizes_of(config()), KIND)
    assert found["step"][1] == 3 * L and found["chunk"][1] == 2 * L
    assert found["step"][0] == pytest.approx(
        3 * L * _sum(DECODE_LAYER, "step", "update"))
    assert found["chunk"][0] == pytest.approx(
        2 * L * _sum(CHUNK_LAYER, "chunk", "write"))
    assert found["conv"] == pytest.approx(
        3 * L * _sum(DECODE_LAYER, "conv") + 2 * L * _sum(CHUNK_LAYER,
                                                          "conv"))
    assert found["gate"] == pytest.approx(2 * L * _sum(CHUNK_LAYER, "gate"))


def test_the_three_readers_on_a_synthetic_window():
    events = _events()
    obs = _observed(events)
    # (laid end to end here, the `while` container counts as busy time of
    # its own; in a trace it covers its body's operations)
    busy = sum(e.dur_ns for e in events) / 1e9
    mine = 3 * L * _sum(DECODE_LAYER, "conv", "step", "update") \
        + 2 * L * _sum(CHUNK_LAYER, "conv", "gate", "chunk", "write")
    assert reader("kda_share_pct")(obs) == pytest.approx(100 * mine / busy)
    least, _ = flops_kda.step_least_seconds(48, H, D, KIND)
    assert reader("kda_step_roofline_pct")(obs) == pytest.approx(
        100 * least / _sum(DECODE_LAYER, "step", "update"))
    least, _ = flops_kda.chunk_least_seconds(200, 64, H, D, KIND)
    assert reader("kda_chunk_roofline_pct")(obs) == pytest.approx(
        100 * least / _sum(CHUNK_LAYER, "chunk", "write"))
    for name in READERS:
        assert 0 < reader(name)(obs) < 100


def test_the_held_experts_share_reads_this_cells_labels():
    """`moe_held_share_pct`, the accepted reader unedited, on the labels of
    this cell's expert layers with the configuration file's keys: the 16
    held experts' stacked products, and the weighted sum `bf16[rows, D]`
    where it takes longer than a quarter of their weights' read (38 us);
    the mixers' and the dense layer's `bf16[64,2560]` are shorter."""
    expert_layer = [
        ("convolution_bitcast_fusion.3 fusion bf16[16,64,768]", 85_000, 1),
        ("fusion.115 fusion bf16[64,2560]", 169_000, 1),
        ("fusion.7 fusion bf16[64,2560]", 2_800, None),
        ("fusion.671 fusion bf16[16,768,256]", 108_000, 1),
        ("fusion.301 fusion bf16[256,2560]", 250_000, 1)]
    events, at = _events(), 5e8
    for label, dur, _ in expert_layer:
        events.append(Event(DEV, OPS_LINE, label, at, float(dur)))
        at += dur + 10
    busy = sum(e.dur_ns for e in events) / 1e9
    # with a weighted sum found at 64 and at 256 rows, the stacked products
    # the two sample layers already hold count too
    stacked = (3 * L * 70_000 + 2 * L * 150_000) / 1e9
    assert reader("moe_held_share_pct")(_observed(events)) == pytest.approx(
        100 * (_sum(expert_layer, 1) + stacked) / busy)
    assert reader("moe_held_share_pct")(_observed(_events())) is None


def test_a_window_of_decode_steps_alone_has_no_chunk_roofline():
    obs = _observed(_events(chunks=0))
    assert reader("kda_chunk_roofline_pct")(obs) is None
    assert reader("kda_step_roofline_pct")(obs) > 0


@pytest.mark.parametrize("name", READERS)
def test_a_reader_says_nothing_on_another_cells_run(name):
    """A parent's program or another cell's: the recorded trace of a
    gpt2-large cell with its configuration and a stats snapshot without
    the family's counters, the new cell's configuration on that trace, a
    run without a trace, and a run whose snapshots are missing."""
    with open(os.path.join(ROOT, "benchmark", "selftest",
                           "trace_sample.json")) as f:
        events = [Event(*e) for e in json.load(f)["events"]]
    gpt2 = {"stats": {"running": 0, "waiting": 0, "steps": 10,
                      "context": {"decode": {"slots_read": 5}}}}
    base = {"device_kind": KIND, "events": events, "before": gpt2,
            "after": gpt2}
    assert reader(name)({**base, "config": config("gpt2-large")}) is None
    assert reader(name)({**base, "config": config()}) is None
    assert reader(name)({**base, "config": config(), "events": None}) is None
    assert reader(name)({"config": config(), "device_kind": KIND,
                         "events": events}) is None
    assert reader(name)({**_observed(_events()),
                         "config": config("granite-4.0-h-small")}) is None
