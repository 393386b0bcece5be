"""CPU tests of the state-space readers on the labels of a decode program
whose one-step recurrence is the `ssm_step` Pallas kernel (PR 49): the
custom call returns `(state buffer, y)`, and both families' readers
(`benchmark/ssm_g1_ops.py`, `benchmark/ssm_ops.py`) take the FIRST entry of
a tuple result, so they count it as the state update that the
`add_dynamic-update-slice_fusion` was (`test_ssm_g1_metrics.py` and
`test_ssm_metrics.py` hold that program's labels, and still describe a
prompt's and a chunk's programs). The labels are the v5e compiler's for the
two cells' decode programs (the AOT compile and the traced runs, PR 49).
Run by hand with the rest of `benchmark/selftest`, and by
`tests/test_benchmark_selftests.py`."""

import pytest

from benchmark import flops_ssm, ssm_g1_ops, ssm_ops
from benchmark.selftest import test_ssm_g1_metrics as g1
from benchmark.selftest import test_ssm_metrics as g8

KERNEL_G1 = ("ssm_step.10 custom-call:tpu_custom_call "
             "(f32[9,64,128,64,128], f32[64,128,64])")
KERNEL_G8 = ("ssm_step.9 custom-call:tpu_custom_call "
             "(f32[8,32,64,64,128], f32[32,64,64])")
# one Mamba layer of granite-4.0-h-small's 64-row decode step with the
# kernel; "counted": what `ssm_g1_ops` takes for the step's
DECODE_G1 = [
    ("fusion.481 fusion bf16[64,16768]", 170, False),  # in_proj
    ("fusion.160 fusion bf16[64,8448]", 3, False),  # conv
    ("slice_convert_fusion.31 fusion (f32[64,128], f32[64,128])", 2, True),
    # B and C in slot order, a group's row on its own for the kernel's
    # blocks: with its dimensions of 1 the reader takes it for a short
    # prompt's `[q, H]` (q = 64 = lanes, H = N): 2 us on the scan's side
    ("broadcast_select_fusion.14 fusion (f32[64,1,1,128], f32[64,1,1,128])",
     2, "scan"),
    ("slice_convert_fusion.6 fusion f32[64,8192]", 3, True),
    ("fusion.19 fusion f32[64,128,64]", 4, True),
    ("compare_select_fusion.9 fusion f32[64,128]", 2, True),
    ("fusion.16 fusion f32[64,128]", 2, True),
    ("copy.179 copy f32[64,128]", 1, True),
    # decay and x dt by block of 32 heads, as the kernel takes them
    ("broadcast_select_fusion.26 fusion f32[64,4,32]", 2, False),
    ("select_multiply_fusion.6 fusion f32[64,4,32,64]", 3, False),
    ("multiply_exponential_fusion.6 fusion f32[64,4,32,1]", 2, False),
    ("copy.180 copy f32[64,4,32,1]", 2, False),
    (KERNEL_G1, 800, True),
    ("fusion.15 fusion f32[64,128,64]", 4, True),  # y back in lane order
    ("multiply_reduce_fusion.7 fusion f32[64]", 3, False),  # the gate
    ("fusion.335 fusion (f32[64], bf16[64,4096])", 90, False),  # out_proj
    ("convolution_bitcast_fusion.17 fusion bf16[18,64,768]", 150, False),
    ("fusion.131 fusion bf16[64,4096]", 90, False),
]
STEP_G1_US = sum(us for _, us, counted in DECODE_G1 if counted is True)
SCAN_G1_US = g1.SCAN_US + sum(us for _, us, counted in DECODE_G1
                              if counted == "scan")


def _labels(ops):
    return [(label, us) for label, us, _ in ops]


def test_one_group_reader_takes_the_kernel_for_the_state_update():
    events, total_us = g1.window(decode=_labels(DECODE_G1))
    found = ssm_g1_ops.from_observed(g1.observed(events))
    assert found["step"] == (pytest.approx(STEP_G1_US * 1e-6), 1)
    # the chunk's one-lane write keeps its label and stays a scan's
    assert found["scan"] == (pytest.approx(SCAN_G1_US * 1e-6), {256: 1})
    obs = g1.observed(events, {64: 10}, 640)
    assert g1.reader("ssm_step_share_pct")(obs) == \
        pytest.approx(100 * STEP_G1_US / total_us)
    least, _ = flops_ssm.step_least_seconds(64, g1.H, g1.P, g1.N, g1.G,
                                            g1.KIND)
    assert g1.reader("ssm_step_roofline_pct")(obs) == \
        pytest.approx(100 * least / (STEP_G1_US * 1e-6), rel=1e-6)
    # one pass at 650 GB/s of the 819: about 80, where two fusions read 54
    assert 75 < g1.reader("ssm_step_roofline_pct")(obs) < 85


@pytest.mark.parametrize("family", ["one-group", "several-groups"])
def test_a_kernel_at_the_hbm_peak_reads_a_hundred_and_no_more(family):
    """The kernel alone, taking exactly what the HBM peak allows for every
    slot's state: 100 in the family's roofline share, not more."""
    if family == "one-group":
        least, _ = flops_ssm.step_least_seconds(64, g1.H, g1.P, g1.N, g1.G,
                                                g1.KIND)
        obs = g1.observed([g1.op(KERNEL_G1, 0.0, least * 1e6)], {64: 5}, 320)
        share = g1.reader("ssm_step_roofline_pct")(obs)
    else:
        least, _ = flops_ssm.step_least_seconds(32, g8.H, g8.P, g8.N, g8.G,
                                                g8.KIND)
        obs = g8.observed([g8.op(KERNEL_G8, 0.0, least * 1e6)], {})
        share = g8.reader("ssm_roofline_pct")(obs)
    assert share == pytest.approx(100.0)


def test_several_groups_reader_takes_the_kernel_for_the_state_update():
    """The nemotron_h cut's decode step of 32 rows: the kernel and, in
    slot order, what it takes."""
    ops = [
        ("fusion.182 fusion bf16[32,6144]", 5, "conv"),
        ("fusion.28 fusion f32[32,8,128]", 2, "step"),  # B to slot order
        ("fusion.29 fusion f32[32,8,128]", 2, "step"),
        ("broadcast_select_fusion.14 fusion "
         "(f32[32,8,1,128], f32[32,8,1,128])", 2, None),
        ("fusion.27 fusion f32[32,64,64]", 4, "step"),
        ("compare_select_fusion.14 fusion f32[32,64]", 2, "step"),
        ("bitcast_select_fusion.29 fusion f32[32,8,8]", 2, "step"),
        ("fusion.156 fusion f32[32,8,8,64]", 3, "step"),  # x dt by block
        ("multiply_exponential_fusion.5 fusion f32[32,8,8,1]", 2, None),
        (KERNEL_G8, 210, "step"),
        ("fusion.23 fusion f32[32,64,64]", 4, "step"),
        ("fusion.544 fusion f32[32,4096]", 3, "gate"),
        ("fusion.6 fusion bf16[32,10304]", 40, None),  # in_proj
    ]
    events, t = [], 0.0
    for label, us, _ in ops:
        events.append(g8.op(label, t, us))
        t += us
    found = ssm_ops.from_observed(g8.observed(events, {}))
    step_us = sum(us for _, us, kind in ops if kind == "step")
    assert found["step"] == (pytest.approx(step_us * 1e-6), 1)
    assert found["conv"] == pytest.approx(5e-6)
    assert found["gate"] == pytest.approx(3e-6)
    assert g8.reader("ssm_share_pct")(g8.observed(events, {})) == \
        pytest.approx(100 * (step_us + 5 + 3) / t)
    assert g8.reader("ssm_roofline_pct")(g8.observed(events, {})) \
        is not None
