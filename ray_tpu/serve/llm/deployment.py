"""Serve integration: LLM engine replicas behind a DeploymentHandle.

Each replica of the deployment owns one `LLMEngine` plus a daemon
step-loop thread; `__call__` returns a `TokenSource`, so callers stream
tokens through ``handle.options(stream=True).remote(payload)`` (one
ObjectRef per token event) or over the HTTP proxy's NDJSON path — the same
streaming generator protocol every other serve deployment uses.

Payload schema (JSON-friendly)::

    {"prompt": [1, 2, 3],          # token ids (no tokenizer in-repo)
     "max_tokens": 16,
     "temperature": 0.0,
     "eos_token_id": null | int | [int, ...],
     "echo": false,
     "stream": true}               # false: single final event only

Engine stats ride the replica's ``control`` concurrency group so probes
don't queue behind long-running token streams.
"""

from __future__ import annotations

import threading
from typing import Any, Sequence

from ray_tpu.serve.llm.config import EngineConfig, SamplingParams

# prefix-affinity routing hashes only the prompt's HEAD: requests whose
# prompts agree on their first AFFINITY_PREFIX_LEN tokens (a shared
# system prompt, an RL rollout's common context) rendezvous onto the
# same replica, whose prefix cache then serves them without re-prefill.
# The window is deliberately short — it must cover the *shared* part of
# typical prompts while ignoring their unique tails, and a shared head
# of one page is already worth routing for
AFFINITY_PREFIX_LEN = 16


def prompt_affinity_key(prompt: Sequence[int],
                        prefix_len: int = AFFINITY_PREFIX_LEN) -> str:
    """Stable routing key for a token-id prompt: hash of its first
    `prefix_len` tokens (the whole prompt when shorter). Same chain
    hash the KV pool uses, so 'same key' == 'prefix the replica's cache
    can actually reuse'."""
    from ray_tpu.serve.llm.cache import hash_page

    return format(hash_page(0, [int(t) for t in prompt[:prefix_len]]),
                  "016x")


class TokenSource:
    """One request's token events and its final event, as a stream source
    (`core/stream_push.py`). Through a replica's worker it is PUSHED:
    `stream_to(writer)` enters the request with the writer, the engine's
    loop puts each step's events there and flushes them with the other
    lanes', and this thread sleeps until the final event is sent or the
    consumer lets go. Iterated (a call with backpressure, the local-mode
    runtime, in-process users) it is the generator it always was. Either
    way the request enters the engine when the stream is started, and a
    consumer gone mid-stream releases the decode lane and the KV pages
    instead of generating to max_tokens for nobody."""

    def __init__(self, engine, prompt, sampling: SamplingParams,
                 tokens: bool = True):
        self.engine = engine
        self.prompt = prompt
        self.sampling = sampling
        self.tokens = tokens  # False: the final event alone

    def stream_to(self, writer) -> None:
        stream = self.engine.add_request(
            self.prompt, self.sampling, writer=writer, tokens=self.tokens)
        try:
            writer.wait()
        finally:
            self._let_go(stream)

    def __iter__(self):
        stream = self.engine.add_request(self.prompt, self.sampling)
        try:
            if self.tokens:
                yield from stream
            else:
                for _ in stream:
                    pass
            yield stream.final()
        finally:
            self._let_go(stream)

    def _let_go(self, stream) -> None:
        if stream.final() is None:
            self.engine.abort_request(stream, "client_disconnected")


class LLMServer:
    """Deployment class: one engine per replica (use via
    `build_llm_app`, or wrap with `serve.deployment` yourself)."""

    def __init__(self, engine_config: dict | EngineConfig | None = None,
                 warmup: bool = True, **cfg_kwargs):
        from ray_tpu.serve.llm.engine import LLMEngine

        if isinstance(engine_config, EngineConfig):
            cfg = engine_config
        else:
            merged = dict(engine_config or {})
            merged.update(cfg_kwargs)
            cfg = EngineConfig.from_dict(merged)
        self.engine = LLMEngine(cfg)
        if warmup:
            # replicas come up hot: every bucketed program compiles
            # before the controller's readiness barrier passes, so the
            # first real request never eats an XLA compile
            self.engine.warmup()
        self._alive = True
        self._loop = threading.Thread(
            target=self._step_loop, daemon=True, name="llm-engine-loop")
        self._loop.start()

    def _step_loop(self):
        import time

        engine = self.engine
        idle = engine.phases.phase("idle")
        while self._alive:
            if engine.step():
                continue
            # nothing queued, running or in flight: sleep, and look for
            # work, under the idle phase, so that a poll is in the
            # loop's account and not beside it
            waiting = True
            while waiting and self._alive:
                with idle:
                    time.sleep(0.002)
                    waiting = not engine.has_work()

    def __call__(self, payload: dict | None):
        payload = payload or {}
        prompt = payload.get("prompt")
        if not prompt:
            raise ValueError("payload needs a non-empty 'prompt' "
                             "(list of token ids)")
        return TokenSource(self.engine, prompt,
                           SamplingParams.from_payload(payload),
                           payload.get("stream", True))

    def update_weights(self, version: int, weights) -> dict:
        """Install new engine params (weight hot-swap). `weights` is a
        param pytree, an ObjectRef to one (the learner publishes params
        through the object store; the runtime resolves refs passed as
        actor-call args, and this also resolves one passed inside),
        or a list of refs whose values are pytree chunks to merge.
        Drain-free: in-flight token streams keep running — see
        `LLMEngine.update_weights` for the version/staleness
        contract."""
        import ray_tpu
        from ray_tpu.core.api import ObjectRef

        if isinstance(weights, ObjectRef):
            weights = ray_tpu.get(weights)
        elif (isinstance(weights, (list, tuple)) and weights
              and all(isinstance(w, ObjectRef) for w in weights)):
            parts = ray_tpu.get(list(weights))
            merged: dict = {}
            for p in parts:
                merged.update(p)
            weights = merged
        return self.engine.update_weights(version, weights)

    def engine_stats(self) -> dict:
        return self.engine.stats()

    def check_health(self) -> str:
        """Controller health probe hook (rides the replica's control
        concurrency group): a replica whose step loop died is alive as
        a process but can never finish a stream — report it unhealthy
        so the self-healing loop replaces it."""
        if self._alive and not self._loop.is_alive():
            raise RuntimeError("engine step loop died")
        return "ok"

    def ping(self) -> str:
        return "pong"

    def shutdown_engine(self) -> bool:
        self._alive = False
        return True


def build_llm_app(
    *,
    model: str = "gpt2",
    preset: str = "tiny",
    num_replicas: int = 1,
    engine_config: dict | None = None,
    max_ongoing_requests: int = 32,
    ray_actor_options: dict | None = None,
) -> Any:
    """Bind an LLM application: ``serve.run(build_llm_app(...))``.

    `engine_config` entries override the model/preset shorthand. A
    replica is one engine on one device: where the cluster has TPU
    chips it claims one, unless `ray_actor_options` says otherwise —
    a replica that claims none is kept off the chips by the runtime."""
    import ray_tpu
    from ray_tpu import serve

    cfg = {"model": model, "preset": preset}
    cfg.update(engine_config or {})
    EngineConfig.from_dict(cfg)  # validate in the driver, not the replica
    if ray_actor_options is None and ray_tpu.is_initialized() and \
            ray_tpu.cluster_resources().get("TPU", 0) >= 1:
        ray_actor_options = {"num_tpus": 1}
    dep = serve.deployment(
        LLMServer,
        name=f"llm-{cfg['model']}",
        num_replicas=num_replicas,
        max_ongoing_requests=max_ongoing_requests,
        ray_actor_options=ray_actor_options,
        # the proxy routes {"prompt": [ids]} payloads by prompt-prefix
        # hash so same-prefix requests land on one replica's warm cache
        payload_affinity=True,
    )
    return dep.bind(cfg)
