"""The gated short-convolution operators' share of their roofline in the
traced window, over the calls whose least time the PRODUCTS set: the least
time the chip could take for those calls (`benchmark/flops_conv.py`, one
requirement, the two matrices' read counted) over the device time their
operations took (`benchmark/conv_ops.py` tells which).

Why those calls alone. XLA fetches an operator's two matrices into fast
memory with asynchronous slices issued a layer ahead (`slice-start` /
`slice-done`, memory space 1), so the operator's own events hold no HBM
read of them, and what the trace has of a fetch is its lifetime (issue to
done, 250 us under the layer before), not its transfer time. A call whose
least time is the 33.6 MB read (a decode step's 32 or 64 rows, a prompt's
short last chunk) is then timed WITHOUT what bounds it, and its share
passes 100 (131.6 over all calls on the chip: PERF.md section 6, PR 44,
finding 4): no sound share can be read for it from these events. A call
of 256 rows needs 43.6 us of the MXU against 43.6 us of HBM: the products
set its least time, the products are in its events wherever the weights
came from, so its share cannot pass 100 and says what the products'
events waste. Those calls are nine tenths of the operator's rows and
57% of its time in the cell (a kept trace; my chip runs, PR 44). The
others are in `conv_share_pct` alone. No call of that kind in the trace:
None."""
from benchmark import conv_ops, flops_conv


def read(observed):
    found = conv_ops.from_observed(observed)
    if found is None:
        return None
    s = conv_ops.sizes_of(observed["config"])
    took = least = 0.0
    for rows, (seconds, calls) in found.items():
        # a prompt's or a chunk's call: one lane
        best, bound = flops_conv.conv_operator_least_seconds(
            rows, 1, s["D"], s["K"], observed["device_kind"])
        if bound == "compute":
            took += seconds
            least += calls * best
    return 100.0 * least / took if took > 0 else None
