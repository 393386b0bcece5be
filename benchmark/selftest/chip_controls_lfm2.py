"""The controls of the lfm2 cell's `correct`, on the chip, by hand:

    chiprun -- python3 benchmark/selftest/chip_controls_lfm2.py 11 77

One engine as the cell builds it (the configuration file's `engine` group:
64 lanes, 20,480 pages, the seeded distribution), the harness's four check
requests (`benchmark/kinds/serve.py`: the same lengths, the same draw from
seed + 1, eight tokens with log-probs; the last crosses a chunk's edge)
served by it for each seed, then the parity's own requests past a chunk's
edge (`parity_lfm2.serve_edge`: three at once, the slots' conv rows read
back from the engine's state buffers), and then the cell's own comparison
(`parity_lfm2.compare`: the log-prob tolerance, the half-layer parity
limits and the engine's leg's) against the reference as it is and against
the reference made wrong in one way at a time (the program's side is what
the engine serves, untouched):

    sound         nothing changed: must come out correct
    float8        every matrix product's operands rounded to float8_e4m3
    no_window     the conv window dropped at the chunk's edge: the rows
                  from 256 on computed as a sequence of their own (what a
                  chunk started from zeros gives); the engine's leg must
                  see it in a log-prob and in the slots' rows
    no_routed     the routed experts left out
    wrong_offset  the held experts taken for the router's experts one on
                  (1-8 for 0-7): every routing weight meets a neighbour
    no_bias       the selection made without `expert_bias`
    bf16_ops      operands rounded to bfloat16: what serving in bf16 does,
                  read for its size, not judged

Each control must come out NOT correct, by one limit at least. Prints a
line a seed and control, writes chiprun_out/controls_lfm2.json, and exits
1 if a sound run fails or a control passes. `--only=sound,no_bias` first
runs those alone: a dozen seeds of the sound readings, for a limit's
room, in the time two seeds of all seven take."""

from __future__ import annotations

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main(seeds: list[int], only: list[str] | None = None) -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import parity_lfm2 as parity
    from benchmark import reference_lfm2 as reference
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
    chunk = config["engine"]["prefill_chunk_size"]
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
        edge = parity.serve_edge(
            engine, np.asarray(cases[-1]["prompt"] + cases[-1]["tokens"]),
            chunk, drive=True)
        no_routed = {**params, "layers": [
            {**p, "we_down": jnp.zeros_like(p["we_down"])}
            if "we_down" in p else p for p in params["layers"]]}
        controls = {
            "sound": {},
            "float8": {"operand_dtype": jnp.float8_e4m3fn},
            "no_window": {"drop_window_at": chunk},
            "no_routed": {"reference_params": no_routed},
            "wrong_offset": {"arch": {
                **arch, "expert_offset": arch["expert_offset"] + 1}},
            "no_bias": {"arch": {**arch, "use_expert_bias": False}},
            "bf16_ops": {"operand_dtype": jnp.bfloat16},
        }
        for name, wrong_way in controls.items():
            if only and name not in only:
                continue
            want, readings, over = parity.compare(params, cases, config,
                                                  edge=edge, **wrong_way)
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
        del controls, no_routed, params  # before the next seed's tree
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "controls_lfm2.json"),
              "w") as f:
        json.dump(out, f, indent=1)
    print(f"{wrong} of {len(out)} readings on the wrong side", flush=True)
    return 1 if wrong else 0


if __name__ == "__main__":
    args = sys.argv[1:]
    only = args.pop(0)[len("--only="):].split(",") \
        if args and args[0].startswith("--only=") else None
    sys.exit(main([int(s) for s in args] or [11, 2147483999], only))
