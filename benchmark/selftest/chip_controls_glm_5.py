"""The controls of the glm-5 cell's `correct`, on the chip, by hand:

    chiprun -- python3 benchmark/selftest/chip_controls_glm_5.py 11 77

One engine as the cell builds it (the configuration file's `engine` group:
32 lanes, 24,576 pages of the latent kind, the seeded distribution), the
harness's four check requests (`benchmark/kinds/serve.py`: the same
lengths, the same draw from seed + 1, eight tokens with log-probs) served
by it for each seed, and then the cell's own comparison
(`parity_glm_5.compare`: the log-prob tolerance and the layer parity
limits on 3,072 rows) against the reference as it is and against the
reference made wrong in one way at a time (the program's side is what the
engine serves, untouched):

    sound           nothing changed: must come out correct
    no_selection    every earlier slot attended, the indexer's choice left out
    topk_1024       the 1,024 highest-scored slots, not 2,048
    index_no_rope   the indexer's queries and keys not rotated
    index_half_rope the indexer's rotation over pairs (i, i + 32), not (2i, 2i + 1)
    no_relu         the indexer's relu left out
    k_pe_no_rope    the shared rotated key not rotated
    score_192       scores divided by sqrt(192), the nope width, not sqrt(256)
    wrong_offset    the held experts taken for the router's experts one on
    no_shared       the shared expert left out
    no_scale        routed_scaling_factor taken for 1
    float8          every matrix product's operands rounded to float8_e4m3
    bf16_ops        operands rounded to bfloat16: what serving in bf16 does,
                    read for its size, not judged

Each control must come out NOT correct, by one limit at least. With `long`
as the first argument it serves instead two prompts of 4,096 and 12,288
tokens through the same engine, with thirty prompts of 1,024 to 3,072
beside them so that their decode steps run at the cell's 32 lanes, eight
output tokens each and their log-probs, against the reference's
log-softmax in row blocks (the harness's own four are all shorter than the
2,048 slots a row may attend). Prints a line a seed and control, writes
chiprun_out/controls_glm_5.json, and exits 1 if a sound run fails or a
control passes."""

from __future__ import annotations

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
LONG_PROMPTS = (4096, 12288)
FILLERS = (1024, 1536, 2304, 3072, 2048)  # thirty more, for the other lanes


def main(argv: list[str]) -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import parity_glm_5 as parity
    from benchmark import reference_glm_5 as reference
    from benchmark.kinds.serve import CHECK_MAX_TOKENS, CHECK_PROMPT_LENS
    from ray_tpu.serve.llm.config import EngineConfig, SamplingParams
    from ray_tpu.serve.llm.engine import LLMEngine
    from ray_tpu.serve.llm.runner import adapters

    long = argv[:1] == ["long"]
    seeds = [int(s) for s in argv[long:]] or [11, 2147483999]
    with open(reference._CONFIG) as f:
        config = json.load(f)
    model = config["model"]
    engine = LLMEngine(EngineConfig.from_dict(
        {"model": model["family"], "preset": model["preset"],
         **config["engine"], "seed": 0}))
    init = adapters()[model["family"]].init_fn
    arch = reference.published_arch()
    tolerance = config["logprob_tolerance"]
    controls = {
        "sound": {},
        "no_selection": {"arch": {**arch, "index_topk": None}},
        "topk_1024": {"arch": {**arch, "index_topk": 1024}},
        "index_no_rope": {"arch": {**arch,
                                   "indexer_rope_interleave": "none"}},
        "index_half_rope": {"arch": {**arch,
                                     "indexer_rope_interleave": False}},
        "no_relu": {"arch": {**arch, "index_relu": False}},
        "k_pe_no_rope": {"arch": {**arch, "k_pe_rotated": False}},
        "score_192": {"arch": {**arch,
                               "score_width": arch["qk_nope_head_dim"]}},
        "wrong_offset": {"arch": {
            **arch, "expert_offset": arch["expert_offset"] + 1}},
        "no_shared": {"arch": {**arch, "n_shared_experts": 0}},
        "no_scale": {"arch": {**arch, "routed_scaling_factor": 1.0}},
        "float8": {"operand_dtype": jnp.float8_e4m3fn},
        "bf16_ops": {"operand_dtype": jnp.bfloat16},
    }
    out, wrong = {}, 0
    for version, seed in enumerate(seeds, start=1):
        engine.update_weights(version, init(jax.random.PRNGKey(seed),
                                            engine.model_cfg))
        params = engine.runner.params
        rng = np.random.default_rng(seed + 1)
        lengths = LONG_PROMPTS + FILLERS * 6 if long else CHECK_PROMPT_LENS
        prompts = [rng.integers(1, config["vocab_size"], n).tolist()
                   for n in lengths]
        # all at once: the long prompts' decode steps run at 32 lanes
        streams = [engine.add_request(p, SamplingParams(
            max_tokens=CHECK_MAX_TOKENS, temperature=0.0, logprobs=True))
            for p in prompts]
        while engine.has_work():
            engine.step()
        cases = [{"prompt": p, "tokens": s.final()["token_ids"],
                  "logprobs": s.final()["logprobs"]}
                 for p, s in zip(prompts, streams)]
        if long:
            want = reference.serve_reference(params, None, cases)
            for n, c, w in zip(lengths, cases, want):
                worst = max(abs(a - b) for a, b in zip(c["logprobs"], w))
                key = f"{seed}:long_{n}"
                out[key] = {"logprob_worst": max(
                    worst, out.get(key, {}).get("logprob_worst", 0.0))}
                wrong += worst > tolerance
            for n in sorted(set(lengths)):
                print(seed, f"prompts of {n}: logprob |diff| max "
                      f"{out[f'{seed}:long_{n}']['logprob_worst']:.4f} "
                      f"(tol {tolerance})", flush=True)
            stats = engine.stats()["context_by_kind"]["latent"]
            print(seed, "context", json.dumps(stats), flush=True)
            continue
        for name, wrong_way in controls.items():
            want, readings, over = parity.compare(params, cases, config,
                                                  **wrong_way)
            worst = max(abs(a - b) for c, w in zip(cases, want)
                        for a, b in zip(c["logprobs"], w))
            correct = worst <= tolerance and not over
            out[f"{seed}:{name}"] = {"logprob_worst": worst, **readings,
                                     "over": over, "correct": correct}
            if name != "bf16_ops" and correct != (name == "sound"):
                wrong += 1
            print(seed, name, "correct" if correct else "NOT correct",
                  f"logprob {worst:.4f} (tol {tolerance})",
                  {k: round(v, 5) for k, v in readings.items()}, flush=True)
        del params  # before the next seed's tree
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    name = "controls_glm_5_long.json" if long else "controls_glm_5.json"
    with open(os.path.join(ROOT, "chiprun_out", name), "w") as f:
        json.dump(out, f, indent=1)
    print(f"{wrong} of {len(out)} readings on the wrong side", flush=True)
    return 1 if wrong else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
