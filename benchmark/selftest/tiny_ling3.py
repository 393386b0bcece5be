"""The ling3 family's plain reference and layer parity at the `tiny`
preset's widths, for the CPU rehearsal of its cell
(`test_cell_ling3_cpu.py`): what `benchmark.parity_ling3.serve_reference`
is to the configuration file, with the architecture read off
`Ling3Config.tiny()` instead, 96 parity rows (five chunks of 16, then
sixteen rows as decode steps) and limits a float32 program keeps by
orders of magnitude."""

from benchmark import parity_ling3 as parity
from benchmark import reference_ling3 as reference

LIMIT = 1e-4  # float32 on both sides: every leg reads rounding


def arch() -> dict:
    from ray_tpu.models.ling3 import Ling3Config

    return reference.arch_of(Ling3Config.tiny())


def readings(params, cases: list[dict], rows: int = 96, **control) -> dict:
    from ray_tpu.models.ling3 import Ling3Config

    return parity.layer_parity(
        params, parity.parity_tokens(cases, rows), Ling3Config.tiny(),
        control.pop("arch", None) or arch(), chunk=16, page=8,
        decode_rows=16, **control)


def serve_reference(params, model: dict, cases: list[dict]):
    want = reference.serve_reference(params, model, cases, arch=arch())
    found = readings(params, cases)
    over = {k: v for k, v in found.items() if not v <= LIMIT}
    print("[parity] tiny:", found, "FAILED" if over else "within limits",
          flush=True)
    if over:
        want = [[w - parity.FAILED for w in row] for row in want]
    return want
