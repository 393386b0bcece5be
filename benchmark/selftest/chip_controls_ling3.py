"""The controls of the ling-3.0-flash-vl cell's `correct`, on the chip, by
hand:

    chiprun -- python3 benchmark/selftest/chip_controls_ling3.py 11 77

One engine as the cell builds it (the configuration file's `engine` group:
64 lanes, 32,768 pages of the latent kind, 0.83 GB of KDA state), the
harness's four check requests (`benchmark/kinds/serve.py`: the same
lengths, the same draw from seed + 1, eight tokens with log-probs) served
by it for each seed, and then the cell's own comparison
(`parity_ling3.compare`: the log-prob tolerance and the layer parity
limits on 8,448 rows) against the reference as it is and against the
reference made wrong in one way at a time (the program's side is what the
engine serves, untouched):

    sound           nothing changed: must come out correct
    state_bf16      S rounded to bfloat16 after every token
    other_gate      the gate's other reading: -exp(A_log) softplus(.)
                    clipped at -5
    no_beta         beta left out (1)
    no_l2           the L2 norm of q and k left out
    no_conv         the short convolution left out
    no_group_limit  plain top-8 of 512 in place of 8 within 4 of 8 groups
    wrong_offset    the held experts taken for the router's experts one on
    float8          every matrix product's operands rounded to float8_e4m3
    bf16_ops        operands rounded to bfloat16: what serving in bf16 does,
                    read for its size, not judged

Each control must come out NOT correct, by one limit at least. Prints a
line a seed and control, writes chiprun_out/controls_ling3.json, and exits
1 if a sound run fails or a control passes. `limits` as the first argument
judges nothing: it prints the readings, for setting the limits."""

from __future__ import annotations

import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main(argv: list[str]) -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import parity_ling3 as parity
    from benchmark import reference_ling3 as reference
    from benchmark.kinds.serve import CHECK_MAX_TOKENS, CHECK_PROMPT_LENS
    from ray_tpu.serve.llm.config import EngineConfig, SamplingParams
    from ray_tpu.serve.llm.engine import LLMEngine
    from ray_tpu.serve.llm.runner import adapters

    judged = argv[:1] != ["limits"]
    seeds = [int(s) for s in argv[not judged:]] or [11, 2147483999]
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

    def left_out(name):
        return {"arch": {**arch, "leave_out": (name,)}}

    controls = {
        "sound": {},
        "state_bf16": {"state_dtype": jnp.bfloat16},
        "other_gate": left_out("safe_gate"),
        "no_beta": left_out("beta"),
        "no_l2": left_out("l2"),
        "no_conv": left_out("conv"),
        "no_group_limit": left_out("group_limit"),
        "wrong_offset": {"arch": {
            **arch, "expert_offset": arch["expert_offset"] + 1}},
        "float8": {"operand_dtype": jnp.float8_e4m3fn},
        "bf16_ops": {"operand_dtype": jnp.bfloat16},
    }
    out, wrong = {}, 0
    for version, seed in enumerate(seeds, start=1):
        engine.update_weights(version, init(jax.random.PRNGKey(seed),
                                            engine.model_cfg))
        params = engine.runner.params
        rng = np.random.default_rng(seed + 1)
        prompts = [rng.integers(1, config["vocab_size"], n).tolist()
                   for n in CHECK_PROMPT_LENS]
        streams = [engine.add_request(p, SamplingParams(
            max_tokens=CHECK_MAX_TOKENS, temperature=0.0, logprobs=True))
            for p in prompts]
        while engine.has_work():
            engine.step()
        cases = [{"prompt": p, "tokens": s.final()["token_ids"],
                  "logprobs": s.final()["logprobs"]}
                 for p, s in zip(prompts, streams)]
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
    with open(os.path.join(ROOT, "chiprun_out", "controls_ling3.json"),
              "w") as f:
        json.dump(out, f, indent=1)
    print(f"{wrong} of {len(out)} readings on the wrong side", flush=True)
    return 1 if wrong else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
