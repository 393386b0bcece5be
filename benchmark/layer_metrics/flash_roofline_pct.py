"""The flash-attention kernels' share of their roofline in the traced
steps: the least time the chip could take for the calls seen (from their
shapes and the peaks table) over the device time they took."""
from benchmark import flops
from benchmark.readers import trace_ops

# The three Pallas kernels of ops/flash_attention.py carry no name of
# their own in the trace (jax names the custom call after whatever scope
# it sits in), so they are told apart by what they return: the forward
# (o, lse), dq one tensor, dkv two.
_T = r"bf16\[[0-9,]+\]"
_CALL = r"^\S+ custom-call:tpu_custom_call "
KERNELS = {"fwd": _CALL + rf"\({_T}, f32\[[0-9,]+\]\)$",
           "dq": _CALL + rf"{_T}$",
           "dkv": _CALL + rf"\({_T}, {_T}\)$"}


def read(observed):
    if not observed.get("events"):
        return None
    sizes, traffic = observed["sizes"], observed["traffic"]
    shape = (traffic["batch_per_chip"], sizes["n_head"], traffic["seq"],
             sizes["n_embd"] // sizes["n_head"])
    took = least = 0.0
    for kernel, pattern in KERNELS.items():
        seconds, calls = trace_ops(observed, pattern)
        if not calls:
            return None
        took += seconds
        best, _ = flops.least_seconds(
            flops.flash_call_flops(kernel, *shape),
            flops.flash_call_bytes(kernel, *shape), observed["device_kind"])
        least += calls * best
    return 100.0 * least / took
