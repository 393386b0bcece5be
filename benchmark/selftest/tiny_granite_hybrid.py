"""The granite_hybrid family's reference and parity legs at the `tiny`
preset's widths, for the CPU rehearsal of its cell
(`test_cell_granite_hybrid_cpu.py`): what
`benchmark.parity_granite_hybrid.serve_reference` is to the configuration
file, with the architecture read off `GraniteHybridConfig.tiny()`
instead."""

from benchmark import parity_granite_hybrid as parity
from benchmark import reference_granite_hybrid as reference

# float32 at `tiny`: what is left is the order of a float32 sum
LIMITS = dict.fromkeys(parity.READINGS + parity.EDGE_READINGS, 1e-4)


def arch() -> dict:
    from ray_tpu.models.granite_hybrid import GraniteHybridConfig

    return reference.arch_of(GraniteHybridConfig.tiny())


def config(chunk: int) -> dict:
    return {"model": {"config": "ray_tpu.models.granite_hybrid:"
                                "GraniteHybridConfig.tiny"},
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
