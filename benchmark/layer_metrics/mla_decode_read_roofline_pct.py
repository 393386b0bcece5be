"""A decode step's dense latent read's share of its roofline in the traced
window, the Pallas kernel's (`ray_tpu/ops/paged_attention.py`, custom call
`ctx_read_paged`, one a layer and decode step since PR 52): the least time
the chip could take for the decode steps the trace holds, each at the
window's mean of what a step had to read (`mla_dense_ops.window_means`:
every (row, cached slot) pair's per-head products and each cached latent
row once a layer, 576 lanes, the greater at the chip's peaks:
`flops_mla_dense.program_least_seconds`, the floor `mla_dense_roofline_pct`
holds the loops to), over the device time of the kernel's calls on the
first device. Decode steps in the trace = calls / `num_hidden_layers`. A
step is bound by memory, and the floor counts 576 of the 640 lanes a row
copies, so the share cannot pass 90.

None where the configuration has no latent kind read whole
(`mla_dense_ops.applies`), and on a trace with no such call: a program
whose decode steps read with the tile loops (every program before PR 52),
which `mla_dense_roofline_pct` reads."""
from benchmark import flops_mla_dense, mla_dense_ops
from benchmark.readers import trace_ops

KERNEL = r"^ctx_read_paged(\.\d+)? custom-call:tpu_custom_call "


def read(observed):
    cfg = observed["config"]
    if not mla_dense_ops.applies(cfg):
        return None
    took, calls = trace_ops(observed, KERNEL) or (0.0, 0)
    if not calls or not took > 0:
        return None
    mean = mla_dense_ops.window_means(observed)
    if mean is None:
        return None
    least, _ = flops_mla_dense.program_least_seconds(
        cfg, mean["decode"]["pairs"], mean["decode"]["slots"],
        observed["device_kind"])
    return 100.0 * calls / cfg["num_hidden_layers"] * least / took
