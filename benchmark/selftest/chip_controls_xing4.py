"""The controls of the xing4 cell's `correct`, on the chip, by hand:

    chiprun -- python3 benchmark/selftest/chip_controls_xing4.py 11 77

One engine as the cell builds it (the configuration file's `engine` group:
32 lanes, 49,152 pages of the latent kind, the seeded distribution), the
harness's four check requests (`benchmark/kinds/serve.py`: the same
lengths, the same draw from seed + 1, eight tokens with log-probs) served
by it for each seed, and then the cell's own comparison
(`parity_xing4.compare`: the log-prob tolerance and the layer parity
limits on 8,448 rows) against the reference as it is and against the
reference made wrong in one way at a time (the program's side is what the
engine serves, untouched):

    sound           nothing changed: must come out correct
    sinkhorn_2      2 Sinkhorn iterations in place of 20
    h_post_1        the factor 2 of H_post left out
    alpha_0         the maps' dynamic part left out (alpha = 0)
    plain_rope      plain frequencies in place of YaRN's (mscale^2 kept)
    no_mscale       mscale^2 left out of the softmax scale
    no_scale        routed_scaling_factor taken for 1
    wrong_offset    the held experts taken for the router's experts one on
    no_shared       the shared expert left out
    bf16_coef       the maps' inputs, phi and logits rounded to bfloat16
    float8          every matrix product's operands rounded to float8_e4m3
    bf16_ops        operands rounded to bfloat16: what serving in bf16 does,
                    read for its size, not judged

Each control must come out NOT correct, by one limit at least. With `long`
as the first argument it serves instead two prompts of 8,192 and 24,576
tokens through the same engine, with thirty prompts of 1,024 to 3,072
beside them so that their decode steps run at the cell's 32 lanes, eight
output tokens each and their log-probs, against the reference's
log-softmax in row blocks (the harness's own four are all shorter than one
tile of the read and than YaRN's original 4,096 positions). Prints a line
a seed and control, writes chiprun_out/controls_xing4.json, and exits 1 if
a sound run fails or a control passes. `limits` as the first argument
judges nothing: it prints the readings, for setting the limits."""

from __future__ import annotations

import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
LONG_PROMPTS = (8192, 24576)
FILLERS = (1024, 1536, 2304, 3072, 2048)  # thirty more, for the other lanes


def main(argv: list[str]) -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import parity_xing4 as parity
    from benchmark import reference_xing4 as reference
    from benchmark.kinds.serve import CHECK_MAX_TOKENS, CHECK_PROMPT_LENS
    from ray_tpu.serve.llm.config import EngineConfig, SamplingParams
    from ray_tpu.serve.llm.engine import LLMEngine
    from ray_tpu.serve.llm.runner import adapters

    long = argv[:1] == ["long"]
    judged = argv[:1] != ["limits"]
    seeds = [int(s) for s in argv[long or not judged:]] or [11, 2147483999]
    with open(reference._CONFIG) as f:
        config = json.load(f)
    model = config["model"]
    t0 = time.monotonic()
    engine = LLMEngine(EngineConfig.from_dict(
        {"model": model["family"], "preset": model["preset"],
         **config["engine"], "seed": 0}))
    print(f"engine built in {time.monotonic() - t0:.1f} s", flush=True)
    init = adapters()[model["family"]].init_fn
    arch = reference.published_arch()
    tolerance = config["logprob_tolerance"]
    controls = {
        "sound": {},
        "sinkhorn_2": {"arch": {**arch, "hc_sinkhorn_iters": 2}},
        "h_post_1": {"arch": {**arch, "h_post_factor": 1.0}},
        "alpha_0": {"arch": {**arch, "hc_dynamic": False}},
        # plain frequencies alone: the softmax's scale stays YaRN's
        "plain_rope": {"arch": {
            **arch, "rope_scaling": None,
            "score_scale": reference.score_scale(arch)}},
        "no_mscale": {"arch": {**arch, "mscale_squared": False}},
        "no_scale": {"arch": {**arch, "routed_scaling_factor": 1.0}},
        "wrong_offset": {"arch": {
            **arch, "expert_offset": arch["expert_offset"] + 1}},
        "no_shared": {"arch": {**arch, "n_shared_experts": 0}},
        "bf16_coef": {"arch": {**arch, "coef_dtype": "bfloat16"}},
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
            t1 = time.monotonic()
            want, readings, over = parity.compare(params, cases, config,
                                                  **wrong_way)
            worst = max(abs(a - b) for c, w in zip(cases, want)
                        for a, b in zip(c["logprobs"], w))
            correct = worst <= tolerance and not over
            out[f"{seed}:{name}"] = {"logprob_worst": worst, **readings,
                                     "over": over, "correct": correct}
            if judged and name != "bf16_ops" \
                    and correct != (name == "sound"):
                wrong += 1
            print(seed, name, "correct" if correct else "NOT correct",
                  f"logprob {worst:.4f} (tol {tolerance})",
                  {k: float(f"{v:.4g}") for k, v in readings.items()},
                  f"{time.monotonic() - t1:.0f} s", flush=True)
        del params  # before the next seed's tree
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    name = "controls_xing4_long.json" if long else "controls_xing4.json"
    with open(os.path.join(ROOT, "chiprun_out", name), "w") as f:
        json.dump(out, f, indent=1)
    print(f"{wrong} of {len(out)} readings on the wrong side", flush=True)
    return 1 if wrong else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
