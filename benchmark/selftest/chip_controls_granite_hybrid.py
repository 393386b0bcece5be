"""The controls of the granite-4.0-h-small cell's `correct`, on the chip,
by hand:

    chiprun -- python3 benchmark/selftest/chip_controls_granite_hybrid.py 11 77

One engine as the cell builds it (the configuration file's `engine` group:
64 lanes, 7,232 pages, the seeded distribution), the harness's four check
requests (`benchmark/kinds/serve.py`: the same lengths, the same draw from
seed + 1, eight tokens with log-probs; the last crosses a chunk's edge)
served by it for each seed, then the parity's own requests past a chunk's
edge (`parity_granite_hybrid.serve_edge`: three at once, the SSM state
compared in the engine's slots), and then the cell's own comparison
(`parity_granite_hybrid.compare`: the log-prob tolerance, the half-layer
parity limits and the engine's leg's) against the reference as it is and
against the reference made wrong in one way at a time (the program's side
is what the engine serves, untouched):

    sound         nothing changed: must come out correct
    float8        every matrix product's operands rounded to float8_e4m3
    bf16_state    the recurrent state rounded to bfloat16 after every
                  token: the state legs must see it
    no_state      the state dropped at the chunk's edge: the rows from 256
                  on computed as a sequence of their own
    no_routed     the routed experts left out (the shared MLP stays)
    wrong_offset  the held experts taken for the router's experts one on
    unscaled      `residual_multiplier` 1: a half-layer's own output is
                  the same, so only the log-probs can see it
    bf16_ops      operands rounded to bfloat16: what serving in bf16 does,
                  read for its size, not judged

Each control must come out NOT correct, by one limit at least. Prints a
line a seed and control, and what the seeded distribution gives (the
logits' spread, a token's pull on its own logit, the load's imbalance),
writes chiprun_out/controls_granite_hybrid.json, and exits 1 if a sound
run fails or a control passes. `--only=sound,bf16_state` first runs those
alone, and with `--full=2` before it from the third seed on (the first
two get every control): a dozen seeds of the sound readings, for a
limit's room, in the time two seeds of all take."""

from __future__ import annotations

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def distribution(params, tokens, arch, reference) -> dict:
    """What `assumed.weights` argues, read off one reference forward: the
    spread of a row's logits, how far a token's own logit stands above the
    row's mean in spreads, and the most loaded expert over the mean."""
    import jax.numpy as jnp
    import numpy as np

    logits, chosen = reference.forward(params, jnp.asarray(tokens), arch)
    logits = np.asarray(logits)[:, :arch["vocab_size"]]
    spread = logits.std(axis=-1)
    own = logits[np.arange(len(tokens)), np.asarray(tokens)]
    pairs = np.bincount(np.asarray(chosen).ravel())
    return {"logit_spread": float(spread.mean()),
            "own_logit_in_spreads": float(np.mean(
                (own - logits.mean(axis=-1)) / spread)),
            "top_minus_mean_in_spreads": float(np.mean(
                (logits.max(axis=-1) - logits.mean(axis=-1)) / spread)),
            "load_imbalance": float(pairs.max() / pairs.mean())}


def main(seeds: list[int], only: list[str] | None = None,
         full: int = 0) -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import parity_granite_hybrid as parity
    from benchmark import reference_granite_hybrid as reference
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
        drawn = distribution(params, cases[-1]["prompt"], arch, reference)
        out[f"{seed}:distribution"] = drawn
        print(seed, "distribution", {k: round(v, 4)
                                     for k, v in drawn.items()}, flush=True)
        no_routed = {**params, "layers": [
            {**p, "we_down": jnp.zeros_like(p["we_down"])}
            for p in params["layers"]]}
        controls = {
            "sound": {},
            "float8": {"operand_dtype": jnp.float8_e4m3fn},
            "bf16_state": {"state_dtype": jnp.bfloat16},
            "no_state": {"drop_state_at": chunk},
            "no_routed": {"reference_params": no_routed},
            "wrong_offset": {"arch": {
                **arch, "expert_offset": arch["expert_offset"] + 1}},
            "unscaled": {"arch": {**arch, "residual_multiplier": 1.0}},
            "bf16_ops": {"operand_dtype": jnp.bfloat16},
        }
        for name, wrong_way in controls.items():
            if only and version > full and name not in only:
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
    with open(os.path.join(ROOT, "chiprun_out",
                           "controls_granite_hybrid.json"), "w") as f:
        json.dump(out, f, indent=1)
    print(f"{wrong} of {len(out) - len(seeds)} readings on the wrong side",
          flush=True)
    return 1 if wrong else 0


if __name__ == "__main__":
    args = sys.argv[1:]
    full = int(args.pop(0)[len("--full="):]) \
        if args and args[0].startswith("--full=") else 0
    only = args.pop(0)[len("--only="):].split(",") \
        if args and args[0].startswith("--only=") else None
    sys.exit(main([int(s) for s in args] or [11, 2147483999], only, full))
