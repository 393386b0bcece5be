"""The lfm2 family's reference and parity leg at the `tiny` preset's
widths, for the CPU rehearsal of its cell (`test_cell_lfm2_cpu.py`): what
`benchmark.parity_lfm2.serve_reference` is to the configuration file, with
the architecture read off `Lfm2Config.tiny()` instead."""

from benchmark import parity_lfm2 as parity
from benchmark import reference_lfm2 as reference

# float32 at `tiny`: what is left is the order of a float32 sum
LIMITS = dict.fromkeys(parity.READINGS + parity.EDGE_READINGS, 1e-4)


def arch() -> dict:
    from ray_tpu.models.lfm2 import Lfm2Config

    return reference.arch_of(Lfm2Config.tiny())


def config(chunk: int) -> dict:
    return {"model": {"config": "ray_tpu.models.lfm2:Lfm2Config.tiny"},
            "engine": {"prefill_chunk_size": chunk},
            "layer_parity": {"rows": 75, "limits": LIMITS}}


def serve_reference(params, model: dict, cases: list[dict]):
    want, readings, over = parity.compare(
        params, cases, config(32), arch=arch(),
        edge=parity.serve_edge_beside(params, cases, 32))
    print("[parity]", readings, over, flush=True)
    if over:
        want = [[w - parity.FAILED for w in row] for row in want]
    return want
