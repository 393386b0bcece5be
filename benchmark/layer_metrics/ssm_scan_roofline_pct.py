"""The chunked form's share of its roofline in the traced window, for a
one-group Mamba-2 mixer: the least time the chip could take for the chunks
seen (`benchmark/flops_ssm.py`) over the device time the scans' operations
took (`benchmark/ssm_g1_ops.py` tells which).

Least time, for each chunk of q rows that a layer's chunked form ran (256
in a chunk's program, a shorter prompt's bucket in a prompt's): the
products the algorithm needs at q rows, the rows' inputs and outputs, and
the lane's state read and written once (a program of `prefill_chunk_size`
rows that runs several chunks of `mamba_chunk_size` reads and writes it
once for all of them, so each is given its share)."""
from benchmark import flops_ssm, ssm_g1_ops
from benchmark.flops import least_seconds


def read(observed):
    found = ssm_g1_ops.from_observed(observed)
    if found is None:
        return None
    s = ssm_g1_ops.sizes_of(observed["config"])
    took, chunks = found["scan"]
    if not chunks or not took > 0:
        return None
    shape = (s["H"], s["P"], s["N"])
    most = max(1, observed["config"]["engine"]["prefill_chunk_size"]
               // s["Q"])
    least = 0.0
    for q, n in chunks.items():
        share = 1.0 / most if q == s["Q"] else 1.0
        least += n * least_seconds(
            flops_ssm.scan_flops(q, q, *shape, s["G"]),
            share * flops_ssm.state_bytes(1, *shape)
            + flops_ssm.rows_bytes(q, *shape, s["G"]),
            observed["device_kind"])[0]
    return 100.0 * least / took
