"""CPU tests of the nemotron_h cell's readers (PR 32): the required
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

from benchmark import flops_moe_held, flops_ssm, ssm_ops  # noqa: E402
from benchmark.trace_reduce import OPS_LINE, Event  # noqa: E402

H, P, N, G, Q = 64, 64, 128, 8, 128
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
                           "nemotron-3-nano-30b-a3b.json")) as f:
        return json.load(f)


def test_flops_and_bytes_by_hand():
    # a lane's state of one layer: 64 x 64 x 128 float32, read and written
    assert flops_ssm.state_bytes(1, H, P, N) == 2 * 524_288 * 4
    # 32 lanes: 134.2 MB a layer, 1.07 GB over 8 layers (ISSUE 32: "1.1")
    assert flops_ssm.state_bytes(32, H, P, N) == 134_217_728
    # a chunk of 128 rows: C B^T 2 x 128^2 x 128 x 8, the masked product
    # 2 x 128^2 x 64 x 64, read-out and update 2 x 2 x 128 x 64 x 128 x 64
    assert flops_ssm.scan_flops(128, 128, H, P, N, G) == \
        33_554_432 + 134_217_728 + 268_435_456
    assert flops_ssm.scan_flops(256, 128, H, P, N, G) == 2 * 436_207_616
    assert flops_ssm.scan_flops(32, 128, H, P, N, G) == \
        2 * 32 * 32 * (1024 + 4096) + 4 * 32 * 64 * 128 * 64
    # a 32-lane step: 67 MFLOP against 134 MB: memory, 0.164 ms
    t, bound = flops_ssm.step_least_seconds(32, H, P, N, G, KIND)
    assert bound == "memory" and t == pytest.approx(0.1647e-3, rel=1e-3)
    # an expert here is two matrices of 2688 x 1856: 9,977,856 parameters
    assert flops_moe_held.held_layer_bytes(1, 0, 2688, 1856) == 19_955_712
    assert flops_moe_held.held_layer_flops(1, 2688, 1856) == 19_955_712
    # a decode step, 48 pairs on 25 held experts touched: 0.609 ms of reads
    t, bound = flops_moe_held.held_layer_least_seconds(48, 25, 32, 2688,
                                                       1856, KIND)
    assert bound == "memory" and t == pytest.approx(0.6095e-3, rel=1e-3)


def op(label, start_us, dur_us):
    return Event(DEV, OPS_LINE, label, start_us * 1e3, dur_us * 1e3)


def window():
    """One decode step's and one 256-row chunk's worth of one Mamba and
    one expert layer, back to back."""
    t, events = 0.0, []
    for label, dur in [
            # decode: conv, slot order, the state update, y, the gate
            ("fusion.1 fusion bf16[32,6144]", 5),
            ("fusion.2 fusion f32[32,64,64]", 10),
            ("fusion.3 fusion f32[32,8,128]", 4),
            ("fusion.4 fusion f32[8,32,64,64,128]", 200),
            ("fusion.5 fusion f32[32,4096]", 3),
            ("fusion.6 fusion bf16[32,10304]", 40),  # in_proj: not counted
            ("fusion.7 fusion f32[32,128]", 2),  # the router: not counted
            ("fusion.8 fusion bf16[32,2688]", 850),  # the experts, fused
            ("fusion.9 fusion bf16[32,2688]", 30),  # out_proj: under the floor
            # chunk: conv, two chunks of 128, the lane's state written
            ("fusion.10 fusion bf16[256,6144]", 20),
            ("fusion.11 fusion f32[8,128,128]", 1),
            ("fusion.12 fusion f32[128,8,8,64]", 5),
            ("fusion.13 fusion f32[8,8,64,128]", 2),
            ("fusion.11 fusion f32[8,128,128]", 1),
            ("fusion.12 fusion f32[128,8,8,64]", 5),
            ("fusion.13 fusion f32[8,8,64,128]", 2),
            ("fusion.14 fusion f32[8,32,64,64,128]", 6),  # one lane written
            ("fusion.15 fusion f32[256,4096]", 8),
            ("fusion.16 fusion bf16[32,1856,256]", 460),
            ("fusion.17 fusion bf16[256,2688]", 500)]:
        events.append(op(label, t, dur))
        t += dur
    return events, t


def observed(events, moe_after):
    zero = {k: 0 for k in ("pairs", "held_pairs", "held_experts_touched",
                           "experts_touched", "layer_calls")}
    zero["expert_pairs"] = [0] * 128
    return {"config": config(), "events": events, "device_kind": KIND,
            "polls": [{"running": 32, "max_batch_size": 32}] * 3,
            "before": {"stats": {"moe": {"decode": dict(zero),
                                         "prefill": dict(zero)}}},
            "after": {"stats": {"moe": moe_after}}}


def counters(pairs, held, touched, calls):
    return {"pairs": pairs, "held_pairs": held,
            "held_experts_touched": touched, "experts_touched": 4 * touched,
            "layer_calls": calls, "expert_pairs": [pairs // 128] * 128}


def test_readers_on_a_synthetic_window():
    events, total_us = window()
    obs = observed(events, {"decode": counters(192_000, 48_000, 25_000, 1000),
                            "prefill": counters(153_600, 38_400, 3_200, 100)})
    found = ssm_ops.from_observed(obs)
    assert found["step"] == (pytest.approx(214e-6), 1)
    assert found["scan"] == (pytest.approx(22e-6), {128: 2})
    assert found["conv"] == pytest.approx(25e-6)
    assert found["gate"] == pytest.approx(11e-6)
    assert reader("ssm_share_pct")(obs) == \
        pytest.approx(100 * (214 + 22 + 25 + 11) / total_us)
    # least: one 32-lane step 164.7 us; two chunks of 128 rows, each half
    # of a lane's state (2.1 MB) and its rows' 2.6 MB: 5.8 us
    step, _ = flops_ssm.step_least_seconds(32, H, P, N, G, KIND)
    chunk = (0.5 * 4_194_304 + flops_ssm.rows_bytes(128, H, P, N, G)) / 819e9
    assert reader("ssm_roofline_pct")(obs) == \
        pytest.approx(100 * (step + 2 * chunk) / 236e-6, rel=1e-6)
    assert reader("moe_held_pairs_share_pct")(obs) == pytest.approx(25.0)
    # decode: 32 x 6 x 0.25 = 48 pairs on 25 touched; chunk: 384 on 32
    dec, _ = flops_moe_held.held_layer_least_seconds(48, 25, 32, 2688, 1856,
                                                     KIND)
    pre, _ = flops_moe_held.held_layer_least_seconds(384, 32, 256, 2688,
                                                     1856, KIND)
    assert reader("moe_held_roofline_pct")(obs) == \
        pytest.approx(100 * (dec + pre) / (850e-6 + 960e-6), rel=1e-6)
    assert reader("moe_held_share_pct")(obs) == \
        pytest.approx(100 * (850 + 960) / total_us)


@pytest.mark.parametrize("name", ["ssm_share_pct", "ssm_roofline_pct",
                                  "moe_held_roofline_pct",
                                  "moe_held_share_pct",
                                  "moe_held_pairs_share_pct"])
def test_a_parent_without_the_family_reads_nothing(name):
    """On the parent, and in every other cell: no such configuration keys,
    no `held_pairs` among the counters, no trace: None, and no raise."""
    olmoe = {"config": {"num_experts": 64, "engine": {"max_batch_size": 16}},
             "events": window()[0], "device_kind": KIND, "polls": [],
             "before": {"stats": {}}, "after": {"stats": {"moe": {
                 "decode": {"pairs": 8, "experts_touched": 8,
                            "layer_calls": 1, "expert_pairs": [1] * 64}}}}}
    assert reader(name)(olmoe) is None
    assert reader(name)({"config": config(), "device_kind": KIND}) is None
