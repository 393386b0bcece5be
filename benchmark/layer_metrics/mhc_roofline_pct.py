"""The coefficient maps' share of their roofline in the traced window: the
least time the chip could take to read each row's state once, `phi` once
a program and half-layer and the logits and coefficients
(`benchmark/flops_mhc.py`: bound by the HBM peak), over the device time of
the operations that do it (`mhc_ops.maps_ops`: the product with `phi` and
the `mhc_maps` kernel). The trace says which programs ran, the window's
counters how many rows a program of each kind had. The product runs at
the highest float32 precision on 24 columns of the MXU and the kernel's
vectors are one sublane of eight, so a low share is expected: the path is
latency-bound in a decode step."""
from benchmark import mhc_ops


def read(observed):
    events = observed.get("events")
    found = mhc_ops.maps_ops(events, observed["config"]) if events else None
    if not found or not found["kernel"][1]:
        return None
    took = mhc_ops.seconds(found)
    best = mhc_ops.least_seconds(observed)
    return 100.0 * best / took if took > 0 and best else None
