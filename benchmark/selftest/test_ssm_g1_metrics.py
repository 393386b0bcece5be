"""CPU tests of the granite-4.0-h-small cell's readers (PR 48): the
required operations and bytes at ONE group against hand-worked numbers,
and the three readers on a synthetic window whose labels are the ones the
v5e compiler gives the cell's programs (the AOT compile of the decode-64
and chunk-256 programs, PR 48). Run by hand with the rest of
`benchmark/selftest`, and by `tests/test_benchmark_selftests.py`."""

import importlib.util
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import flops_ssm, ssm_g1_ops  # noqa: E402
from benchmark.trace_reduce import OPS_LINE, Event  # noqa: E402

H, P, N, G, Q, L, LANES = 128, 64, 128, 1, 256, 9, 64
KIND = "TPU v5 lite"
DEV = "/device:TPU:0"


def reader(name):
    path = os.path.join(ROOT, "benchmark", "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("m_" + name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def config(name="granite-4.0-h-small"):
    with open(os.path.join(ROOT, "benchmark", "configs",
                           name + ".json")) as f:
        return json.load(f)


def test_the_sizes_come_off_the_configuration_file():
    assert ssm_g1_ops.sizes_of(config()) == {
        "H": H, "P": P, "N": N, "G": G, "Q": Q, "L": L, "lanes": LANES}
    # another family's keys, or more groups than one: not this reader's
    assert ssm_g1_ops.sizes_of(config("nemotron-3-nano-30b-a3b")) is None
    assert ssm_g1_ops.sizes_of({**config(), "mamba_n_groups": 8}) is None
    assert ssm_g1_ops.sizes_of(config("gpt2-large")) is None


def test_flops_and_bytes_by_hand():
    # a lane's state of one layer: 128 x 64 x 128 float32, read and written
    assert flops_ssm.state_bytes(1, H, P, N) == 2 * 4_194_304
    # 64 lanes: 536.9 MB a layer, 4.83 GB over 9 layers a step (ISSUE 48:
    # "read AND write 2 x 2.42 GB")
    assert flops_ssm.state_bytes(LANES, H, P, N) == 536_870_912
    assert L * flops_ssm.state_bytes(LANES, H, P, N) == 4_831_838_208
    # a 64-lane step: 268 MFLOP against 539 MB: memory, 0.658 ms a layer
    assert flops_ssm.step_flops(LANES, H, P, N) == 268_435_456
    t, bound = flops_ssm.step_least_seconds(LANES, H, P, N, G, KIND)
    assert bound == "memory"
    assert t == pytest.approx((536_870_912 + 64 * 2 * 16_768) / 819e9)
    assert t == pytest.approx(0.6581e-3, rel=1e-3)
    # a chunk of 256 rows at ONE group: C B^T 2 x 256^2 x 128, the masked
    # product 2 x 256^2 x 64 x 128, read-out and update 2 x 2 x 256 x 64 x
    # 128 x 128
    assert flops_ssm.scan_flops(256, 256, H, P, N, G) == \
        16_777_216 + 1_073_741_824 + 1_073_741_824
    # 2.16 GFLOP: 11.0 us of the MXU, beside 8.4 MB of state and 8.6 MB of
    # rows: 20.7 us of HBM. Memory bounds the chunk's least time
    t, bound = flops_ssm.scan_least_seconds(256, 256, H, P, N, G, KIND)
    assert bound == "memory" and t == pytest.approx(20.72e-6, rel=1e-3)


def op(label, start_us, dur_us):
    return Event(DEV, OPS_LINE, label, start_us * 1e3, dur_us * 1e3)


# one Mamba layer of a 64-row decode step, as the v5e compiler names it
DECODE = [
    ("fusion.481 fusion bf16[64,16768]", 170),  # in_proj: not counted
    ("fusion.9 fusion f32[64,128]", 2),  # dt to slot order
    ("fusion.10 fusion bf16[64,8448]", 3),  # conv: not counted
    ("select_dynamic-update-slice_fusion.15 fusion (bf16[9,64,8448], "
     "bf16[9,64,8448], bf16[9,64,8448], bf16[64,1,8448])", 6),
    ("fusion.140 fusion f32[8448,64]", 4),
    ("slice_convert_fusion.34 fusion (f32[64,128], f32[64,128])", 2),
    ("multiply_exponential_fusion.7 fusion (f32[64,128], f32[64,128], "
     "f32[64,128], f32[64,128])", 2),
    ("slice_convert_fusion.7 fusion f32[64,8192]", 3),
    ("fusion.12 fusion f32[64,128,64]", 4),
    ("select_multiply_fusion.7 fusion f32[64,128,64]", 3),
    ("fusion.119 fusion f32[64,128,64]", 380),  # y: reads the state
    ("add_dynamic-update-slice_fusion.8 fusion f32[9,64,128,64,128]", 700),
    ("fusion.15 fusion f32[64,128,64]", 4),
    ("multiply_reduce_fusion.7 fusion f32[64]", 3),  # the gate's norm
    ("fusion.335 fusion (f32[64], bf16[64,4096])", 90),  # out_proj
    ("fusion.770 fusion (f32[64], f32[64,72])", 3),  # the router
    ("convolution_bitcast_fusion.17 fusion bf16[18,64,768]", 150),
    ("fusion.131 fusion bf16[64,4096]", 90),  # the experts' down
]
# one Mamba layer of a 256-row chunk
CHUNK = [
    ("fusion.527 fusion bf16[256,16768]", 190),  # in_proj
    ("dynamic-update-slice.40 dynamic-update-slice bf16[9,64,8448]", 1),
    ("pad_maximum_fusion.1 fusion bf16[259,8448]", 5),  # conv
    ("fusion.816 fusion f32[256,256]", 3),  # C B^T
    ("fusion.697 fusion (f32[256,128], f32[256,128])", 2),
    ("reduce_window_sum.50 add f32[128,256]", 4),  # the running sums
    ("copy.380 copy f32[128,256]", 1),
    ("constant_dynamic-slice_fusion.5 fusion f32[1,1,128,64,128]", 6),
    ("fusion.435 fusion f32[128,64,256]", 40),  # the state's read-out
    ("multiply_multiply_fusion.7 fusion (bf16[256,8192], f32[256,8192])", 8),
    ("copy.376 copy f32[256,8192]", 7),
    ("fusion.192 fusion f32[256,1,128,64]", 260),  # the masked product
    ("fusion.437 fusion f32[9,64,128,64,128]", 45),  # ONE lane written
    ("multiply_reduce_fusion.7 fusion f32[256]", 4),  # the gate's norm
    ("fusion.304 fusion (f32[256], bf16[256,4096])", 110),  # out_proj
    ("fusion.563 fusion bf16[18,768,256]", 160),  # the experts
    ("fusion.55 fusion bf16[256,4096]", 120),
    # the attention layer's rows are no part of the recurrence
    ("fusion.900 fusion f32[8,4,256,1024]", 30),
    ("fusion.901 fusion bf16[1,256,8,4,128]", 10),
]
STEP_US = 2 + 2 + 2 + 3 + 4 + 3 + 380 + 700 + 4
SCAN_US = 3 + 2 + 4 + 1 + 6 + 40 + 8 + 7 + 260 + 45


def window(decode=DECODE, chunk=CHUNK):
    t, events = 0.0, []
    for label, dur in decode + chunk:
        events.append(op(label, t, dur))
        t += dur
    return events, t


def observed(events, steps=None, lanes=0):
    after = {} if steps is None else {
        "decode_steps": {str(k): v for k, v in steps.items()},
        "decode_lanes": lanes}
    return {"config": config(), "events": events, "device_kind": KIND,
            "before": {"stats": {"state": {"decode_steps": {},
                                           "decode_lanes": 0}}},
            "after": {"stats": {"state": after}}}


def test_which_operations_are_the_recurrences():
    events, _ = window()
    found = ssm_g1_ops.from_observed(observed(events))
    assert found["step"] == (pytest.approx(STEP_US * 1e-6), 1)
    assert found["scan"] == (pytest.approx(SCAN_US * 1e-6), {256: 1})
    # a chunk's program alone: its write of one lane is no step
    found = ssm_g1_ops.from_observed(observed(window(decode=[])[0]))
    assert found["step"] == (0.0, 0) and found["scan"][1] == {256: 1}
    # a short prompt's bucket is its one chunk
    short = [(label.replace("256", "32"), dur) for label, dur in CHUNK]
    found = ssm_g1_ops.from_observed(observed(window([], short)[0]))
    assert found["scan"][1] == {32: 1}
    assert found["scan"][0] == pytest.approx(SCAN_US * 1e-6)
    # nothing of either: not understood
    assert ssm_g1_ops.from_observed(observed(
        window(DECODE[:3], CHUNK[:3])[0])) is None


def test_readers_on_a_synthetic_window():
    events, total_us = window()
    # the window's steps: 10 of 64 rows and 2 of 32, 700 lanes in all
    obs = observed(events, {64: 10, 32: 2}, 700)
    assert reader("ssm_step_share_pct")(obs) == \
        pytest.approx(100 * STEP_US / total_us)
    lanes = 700 / 12
    step, bound = flops_ssm.step_least_seconds(lanes, H, P, N, G, KIND)
    assert bound == "memory"
    assert reader("ssm_step_roofline_pct")(obs) == \
        pytest.approx(100 * step / (STEP_US * 1e-6), rel=1e-6)
    # 58.3 of 64 slots decoding, the state read twice: about half
    assert 50 < reader("ssm_step_roofline_pct")(obs) < 60
    chunk, _ = flops_ssm.scan_least_seconds(256, 256, H, P, N, G, KIND)
    assert reader("ssm_scan_roofline_pct")(obs) == \
        pytest.approx(100 * chunk / (SCAN_US * 1e-6), rel=1e-6)


def test_no_share_can_pass_a_hundred():
    """Operations that take exactly what the HBM peak allows for 64
    running lanes, and a chunk at its least time: 100, not more; with
    fewer lanes running the step's share falls."""
    step, _ = flops_ssm.step_least_seconds(64, H, P, N, G, KIND)
    chunk, _ = flops_ssm.scan_least_seconds(256, 256, H, P, N, G, KIND)
    events, _ = window(
        [("add_dynamic-update-slice_fusion.8 fusion f32[9,64,128,64,128]",
          step * 1e6)],
        [("fusion.192 fusion f32[256,1,128,64]", chunk * 1e6)])
    full = observed(events, {64: 5}, 320)
    assert reader("ssm_step_roofline_pct")(full) == pytest.approx(100.0)
    assert reader("ssm_scan_roofline_pct")(full) == pytest.approx(100.0)
    half = observed(events, {64: 5}, 160)
    assert reader("ssm_step_roofline_pct")(half) == \
        pytest.approx(50.0, rel=5e-3)
    # more lanes than slots cannot be: the counter is capped
    over = observed(events, {64: 5}, 640)
    assert reader("ssm_step_roofline_pct")(over) == pytest.approx(100.0)


@pytest.mark.parametrize("name", ["ssm_step_share_pct",
                                  "ssm_step_roofline_pct",
                                  "ssm_scan_roofline_pct"])
def test_a_parent_or_another_cell_reads_nothing(name):
    """Another configuration's keys, no trace, no state counters, a trace
    with no recurrence in it: None, and no raise."""
    events, _ = window()
    nemotron = {**observed(events, {64: 1}, 64),
                "config": config("nemotron-3-nano-30b-a3b")}
    assert reader(name)(nemotron) is None
    assert reader(name)({"config": config(), "device_kind": KIND}) is None
    assert reader(name)({"config": config(), "device_kind": KIND,
                         "events": []}) is None
    other = observed(window(DECODE[:1], CHUNK[:1])[0], {64: 1}, 64)
    assert reader(name)(other) is None
    if name == "ssm_step_roofline_pct":  # a program older than the counter
        assert reader(name)(observed(events)) is None
