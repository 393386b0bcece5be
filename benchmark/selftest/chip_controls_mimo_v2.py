"""The controls of the mimo-v2.5 cell's `correct`, on the chip, by hand:

    chiprun -- python3 benchmark/selftest/chip_controls_mimo_v2.py 11 77

One engine as the cell builds it (the configuration file's `engine` group:
32 lanes, 12,288 full-kind pages, the window kind's sized off the lanes,
the seeded distribution), the harness's four check requests
(`benchmark/kinds/serve.py`: the same lengths, the same draw from seed + 1,
eight tokens with log-probs) served by it for each seed, and then the
cell's own comparison (`parity_mimo_v2.compare`: the log-prob tolerance and
the layer parity limits on 641 rows) against the reference as it is and
against the reference made wrong in one way at a time (the program's side
is what the engine serves, untouched):

    sound         nothing changed: must come out correct
    no_window     the window left out of the window layers' mask
    no_sink       the sinks left out of the window layers' softmax
    no_value_scale  attention_value_scale taken for 1
    score_128     scores divided by sqrt(128), the v width, not sqrt(192)
    float8        every matrix product's operands rounded to float8_e4m3
    wrong_offset  the held experts taken for the router's experts one on
                  (1-16 for 0-15): every routing weight meets a neighbour
    rotate_all    all 192 dimensions of q and k rotated, not the first 64
    bf16_ops      operands rounded to bfloat16: what serving in bf16 does,
                  read for its size, not judged

Each control must come out NOT correct, by one limit at least. Prints a
line a seed and control, writes chiprun_out/controls_mimo_v2.json, and
exits 1 if a sound run fails or a control passes."""

from __future__ import annotations

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main(seeds: list[int]) -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import parity_mimo_v2 as parity
    from benchmark import reference_mimo_v2 as reference
    from benchmark.kinds.serve import CHECK_MAX_TOKENS, CHECK_PROMPT_LENS
    from ray_tpu.serve.llm.config import EngineConfig, SamplingParams
    from ray_tpu.serve.llm.engine import LLMEngine
    from ray_tpu.serve.llm.runner import adapters

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
        "no_window": {"arch": {**arch, "sliding_window": None}},
        "no_sink": {"arch": {**arch, "add_swa_attention_sink_bias": False}},
        "no_value_scale": {"arch": {**arch, "attention_value_scale": 1.0}},
        "score_128": {"arch": {**arch, "score_width": arch["v_head_dim"]}},
        "float8": {"operand_dtype": jnp.float8_e4m3fn},
        "wrong_offset": {"arch": {
            **arch, "expert_offset": arch["expert_offset"] + 1}},
        "rotate_all": {"arch": {**arch, "partial_rotary_factor": 1.0}},
        "bf16_ops": {"operand_dtype": jnp.bfloat16},
    }
    out, wrong = {}, 0
    for version, seed in enumerate(seeds, start=1):
        engine.update_weights(version, init(jax.random.PRNGKey(seed),
                                            engine.model_cfg))
        params = engine.runner.params
        rng = np.random.default_rng(seed + 1)
        cases = []
        for n in CHECK_PROMPT_LENS:
            prompt = rng.integers(1, config["vocab_size"], n).tolist()
            got = engine.generate(
                prompt, SamplingParams(max_tokens=CHECK_MAX_TOKENS,
                                       logprobs=True), drive=True,
                timeout=900)
            cases.append({"prompt": prompt, "tokens": got["token_ids"],
                          "logprobs": got["logprobs"]})
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
    with open(os.path.join(ROOT, "chiprun_out", "controls_mimo_v2.json"),
              "w") as f:
        json.dump(out, f, indent=1)
    print(f"{wrong} of {len(out)} readings on the wrong side", flush=True)
    return 1 if wrong else 0


if __name__ == "__main__":
    sys.exit(main([int(s) for s in sys.argv[1:]] or [11, 2147483999]))
