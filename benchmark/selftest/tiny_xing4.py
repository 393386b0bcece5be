"""The xing4 family's plain reference and layer parity at the `tiny`
preset's widths, for the CPU rehearsal of its cell
(`test_cell_xing4_cpu.py`): what `benchmark.parity_xing4.serve_reference`
is to the configuration file, with the architecture read off
`Xing4Config.tiny()` instead, 96 parity rows (six chunks of 16, then
sixteen rows as decode steps) and limits a float32 program keeps by
orders of magnitude."""

from benchmark import parity_xing4 as parity
from benchmark import reference_xing4 as reference

LIMIT = 1e-4  # float32 on both sides: every leg reads rounding


def arch() -> dict:
    from ray_tpu.models.xing4 import Xing4Config

    cfg = Xing4Config.tiny()
    found = {k: getattr(cfg, k) for k in reference.ARCH_KEYS
             if hasattr(cfg, k)}
    found["rope_scaling"] = tuple(sorted({
        "type": "yarn", "factor": cfg.rope_factor,
        "beta_fast": cfg.rope_beta_fast, "beta_slow": cfg.rope_beta_slow,
        "mscale": cfg.rope_mscale, "mscale_all_dim": cfg.rope_mscale_all_dim,
        "original_max_position_embeddings":
            cfg.original_max_position_embeddings}.items()))
    return found


def readings(params, cases: list[dict], rows: int = 96) -> dict:
    from ray_tpu.models.xing4 import Xing4Config

    return parity.layer_parity(
        params, parity.parity_tokens(cases, rows), Xing4Config.tiny(),
        arch(), chunk=16, page=8, decode_rows=16)


def serve_reference(params, model: dict, cases: list[dict]):
    want = reference.serve_reference(params, model, cases, arch=arch())
    found = readings(params, cases)
    over = {k: v for k, v in found.items() if not v <= LIMIT}
    print("[parity] tiny:", found, "FAILED" if over else "within limits",
          flush=True)
    if over:
        want = [[w - parity.FAILED for w in row] for row in want]
    return want
