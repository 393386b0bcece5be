"""CPU tests of the routed-expert metrics' arithmetic (PR 27): the required
operations and bytes against hand-worked numbers, and the four readers on a
synthetic window. Run by hand with the rest of `benchmark/selftest`."""

import importlib.util
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import flops_moe  # noqa: E402
from benchmark.trace_reduce import Event  # noqa: E402

D, F = 2048, 1024  # OLMoE-1B-7B: hidden size, one expert's width


def reader(name):
    path = os.path.join(ROOT, "benchmark", "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("m_" + name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def test_flops_and_bytes_by_hand():
    # one pair: 3 products of 2 x 2048 x 1024 = 12,582,912 flops
    assert flops_moe.expert_layer_flops(1, D, F) == 12_582_912
    # a 256-row chunk routes 2,048 pairs: 25.77 GFLOP (ISSUE 27: "26")
    assert flops_moe.expert_layer_flops(2048, D, F) == 25_769_803_776
    # one expert in bf16: 3 x 2048 x 1024 x 2 = 12,582,912 bytes; all 64:
    # 805,306,368 (ISSUE 27: "805 MB"); 16 rows in and out: 131,072 more
    assert flops_moe.expert_layer_bytes(1, 0, D, F) == 12_582_912
    assert flops_moe.expert_layer_bytes(64, 16, D, F) == 805_306_368 + 131_072
    # a decode step of 16 lanes touching 55 experts: 692.2 MB at 819 GB/s
    # is 0.845 ms, against 0.0082 ms of arithmetic: bound by memory
    t, bound = flops_moe.expert_layer_least_seconds(128, 55, 16, D, F,
                                                    "TPU v5 lite")
    assert bound == "memory" and t == pytest.approx(0.8452e-3, rel=1e-3)
    # a 256-row chunk, all 64 touched: 0.9858 ms of reading, 0.1308 ms of
    # arithmetic
    t, bound = flops_moe.expert_layer_least_seconds(2048, 64, 256, D, F,
                                                    "TPU v5 lite")
    assert bound == "memory" and t == pytest.approx(0.9858e-3, rel=1e-3)


def stats(decode_calls, touched, pairs, per_expert, prefill=None):
    moe = {"decode": {"pairs": pairs, "experts_touched": touched,
                      "layer_calls": decode_calls,
                      "expert_pairs": per_expert}}
    if prefill:
        moe["prefill"] = prefill
    return {"stats": {"moe": moe}}


def test_counter_readers_on_a_synthetic_window():
    before = stats(80, 4_400, 10_240, [160] * 64)
    after = stats(880, 48_400, 112_640,
                  [160 + 1_600] * 63 + [160 + 1_600 + 0])
    observed = {"before": before, "after": after}
    # (48,400 - 4,400) / (880 - 80) = 55 experts a layer of a decode step
    assert reader("moe_experts_touched")(observed) == pytest.approx(55.0)
    # every expert took 1,600 pairs in the window: perfectly even
    assert reader("moe_load_imbalance")(observed) == pytest.approx(1.0)
    after["stats"]["moe"]["decode"]["expert_pairs"][0] += 1_600
    # one expert took twice the others': 3,200 / (65 x 1,600 / 64)
    assert reader("moe_load_imbalance")(observed) == \
        pytest.approx(3_200 * 64 / (65 * 1_600))
    # prefill steps count in the imbalance too
    after["stats"]["moe"]["prefill"] = {
        "pairs": 64_000, "experts_touched": 640, "layer_calls": 10,
        "expert_pairs": [1_000] * 64}
    assert reader("moe_load_imbalance")(observed) == \
        pytest.approx(4_200 * 64 / (65 * 1_600 + 64_000))


@pytest.mark.parametrize("name", ["moe_experts_touched",
                                  "moe_load_imbalance",
                                  "moe_expert_share_pct",
                                  "moe_expert_roofline_pct"])
def test_readers_find_nothing_on_a_program_without_the_counters(name):
    """The parent commit's engine has no routing account, a dense model
    an empty one, an untraced run no events: None, never an error."""
    config = {"num_experts": 64, "hidden_size": D, "intermediate_size": F,
              "num_experts_per_tok": 8, "engine": {"max_batch_size": 16}}
    for moe in ({}, None):
        stats_ = {"stats": {} if moe is None else {"moe": moe}}
        observed = {"before": stats_, "after": stats_, "config": config,
                    "device_kind": "TPU v5 lite",
                    "events": [Event("/device:TPU:0", "XLA Ops",
                                     "fusion.1 fusion bf16[8,128]", 0, 1000)]}
        assert reader(name)(observed) is None
    assert reader(name)({"config": config, "events": None}) is None


def moe_trace():
    """Two layers of a 16-lane decode step and one of a 256-row chunk,
    with the labels a v5e trace of the OLMoE cell has (my chip run,
    PR 27), times in ns."""
    d, ops = "/device:TPU:0", "XLA Ops"
    ev, t = [], 0

    def op(name, dur):
        nonlocal t
        ev.append(Event(d, ops, name, t, dur))
        t += dur + 1_000

    for _ in range(2):
        op("fusion.220 fusion bf16[16,2048]", 13_000)  # attention's output
        op("fusion.239 fusion f32[16,64]", 8_000)  # router probabilities
        op("fusion.242 fusion s32[64]", 1_000)  # pairs per expert
        op("fusion.234 fusion bf16[64,1024,16]", 390_000)
        op("fusion.240 fusion bf16[16,2048]", 740_000)
    op("fusion.243 fusion bf16[64,256,1024]", 435_000)
    op("fusion.242 fusion bf16[64,256,1024]", 411_000)
    op("fusion.249 fusion bf16[256,2048]", 417_000)
    op("copy.82 copy bf16[2048,8,16,128]", 200_000)
    ev.append(Event(d, ops, "while.10 while (s32[], bf16[16,2048])", 0, t))
    return ev, t


def test_trace_readers_on_a_synthetic_trace():
    from benchmark import moe_ops

    events, span = moe_trace()
    found = moe_ops.expert_ops(events, 64, F, D)
    assert found["experts"][16] == (pytest.approx(2 * 1.130e-3), 2)
    assert found["experts"][256] == (pytest.approx(1.263e-3), 1)
    assert found["routing"] == pytest.approx(18e-6)  # the two [.., 64] ops
    config = {"num_experts": 64, "hidden_size": D, "intermediate_size": F,
              "num_experts_per_tok": 8, "engine": {"max_batch_size": 16}}
    before = stats(0, 0, 0, [0] * 64, prefill={
        "pairs": 0, "experts_touched": 0, "layer_calls": 0,
        "expert_pairs": [0] * 64})
    after = stats(800, 44_000, 102_400, [1_600] * 64, prefill={
        "pairs": 20_480, "experts_touched": 640, "layer_calls": 10,
        "expert_pairs": [320] * 64})
    observed = {"events": events, "config": config, "before": before,
                "after": after, "device_kind": "TPU v5 lite"}
    busy = span / 1e9  # the loop's own event covers its body's gaps
    share = reader("moe_expert_share_pct")(observed)
    assert share == pytest.approx(
        100 * (2 * 1.130e-3 + 1.263e-3 + 18e-6) / busy)
    # least: a decode call 55 touched experts and 16 rows in and out,
    # 692,191,232 bytes / 819e9 = 0.84517 ms; the chunk 64 experts and 256
    # rows, 807,403,520 bytes = 0.98584 ms
    least = 2 * 692_191_232 / 819e9 + 807_403_520 / 819e9
    roof = reader("moe_expert_roofline_pct")(observed)
    assert roof == pytest.approx(100 * least / (2 * 1.130e-3 + 1.263e-3))
    assert 70 < roof < 80
    # an expert product without its consumer: not understood, not guessed
    broken = [e for e in events if "bf16[256,2048]" not in e.name]
    assert moe_ops.expert_ops(broken, 64, F, D) is None
