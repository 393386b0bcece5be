"""The glm_dsa family's plain reference at the `tiny` preset's widths,
for the CPU rehearsal of its cell (`test_cells_cpu.py`): what
`benchmark.reference_glm_5.serve_reference` is to the configuration file,
with the architecture read off `GlmDsaConfig.tiny()` instead."""

from benchmark import reference_glm_5 as reference


def arch() -> dict:
    from ray_tpu.models.glm_dsa import GlmDsaConfig

    cfg = GlmDsaConfig.tiny()
    return {**{k: getattr(cfg, k) for k in reference.ARCH_KEYS
               if hasattr(cfg, k)},
            "rope_interleave": True, "indexer_rope_interleave": True,
            "rope_theta": cfg.rope_theta}


def serve_reference(params, model: dict, cases: list[dict]):
    return reference.serve_reference(params, model, cases, arch=arch())
