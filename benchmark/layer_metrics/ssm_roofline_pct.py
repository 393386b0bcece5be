"""The recurrence's share of its roofline in the traced window: the least
time the chip could take for the scans and steps seen
(`benchmark/flops_ssm.py`) over the device time their operations took
(`benchmark/ssm_ops.py` tells which).

Least time: for each chunk of q rows that a layer's chunk loop ran, the
chunked form's products at q and the rows' inputs and outputs, with the
lane's state read and written once a program (so a chunk of a program
that may run `prefill_chunk_size / chunk_size` of them is given that share
of it); for each layer of a decode step, one step of the recurrence and
the state of the lanes that were running (the mean of the engine's polled
`running`, never more than `max_batch_size`): the slots no lane owned are
the program's to touch, not the algorithm's."""
from benchmark import flops_ssm, ssm_ops
from benchmark.flops import least_seconds


def read(observed):
    found = ssm_ops.from_observed(observed)
    polls = observed.get("polls")
    if found is None or not polls:
        return None
    s = ssm_ops.sizes_of(observed["config"])
    kind = observed["device_kind"]
    shape = (s["H"], s["P"], s["N"])
    (step_s, updates), (scan_s, chunks) = found["step"], found["scan"]
    lanes = min(sum(p["running"] for p in polls) / len(polls), s["lanes"])
    least = updates * flops_ssm.step_least_seconds(
        lanes, *shape, s["G"], kind)[0]
    most = max(1, observed["config"]["engine"]["prefill_chunk_size"]
               // s["Q"])
    for q, n in chunks.items():
        share = 1.0 / most if q == s["Q"] else 1.0
        least += n * least_seconds(
            flops_ssm.scan_flops(q, q, *shape, s["G"]),
            share * flops_ssm.state_bytes(1, *shape)
            + flops_ssm.rows_bytes(q, *shape, s["G"]), kind)[0]
    took = step_s + scan_s
    return 100.0 * least / took if took > 0 else None
