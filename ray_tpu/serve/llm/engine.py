"""LLMEngine: cache pool + runner + scheduler + streaming outputs.

One engine instance serves one model replica. Requests arrive from any
thread (`add_request` / `generate`); exactly one thread drives
`step()` (the serve deployment runs a daemon step loop; tests call
`step()` inline). Each request gets a `RequestStream` — an iterator of
token events fed by the step loop and closed with a final summary
event.

Engine metrics flow through `ray_tpu.util.metrics`, so every replica's
numbers land on the process /metrics surface the dashboard scrapes:
tokens generated, TTFT, the gap between two tokens by what the loop did
in it, per-step latency, queue depth, cache utilization, preemptions.
"""

from __future__ import annotations

import bisect
import collections
import contextlib
import dataclasses
import itertools
import queue
import sys
import threading
import time
import weakref
from typing import Any, Sequence as Seq

import numpy as np

from ray_tpu.serve.llm.cache import (
    BlockPool,
    KVLayout,
    KVPools,
    StateSlots,
    auto_num_blocks,
    blocks_by_kind,
)
from ray_tpu.serve.llm.config import EngineConfig, SamplingParams
from ray_tpu.serve.llm.runner import (
    DecodeItem,
    Launched,
    ModelRunner,
    adapters,
    chunk_rows,
    state_layout_of,
)
from ray_tpu.serve.llm.scheduler import (
    DecodeWork,
    NeedsResults,
    PrefillWork,
    Scheduler,
    SeqState,
    Sequence,
)
from ray_tpu.util import tracing

_FINAL = object()
# why a step was read with none launched behind it
DRAIN_REASONS = ("speculation", "preempt", "swap", "abort", "idle", "error")
# Upper edges, in ms, of the buckets every duration the loop accounts
# for is counted in (token gaps, turns, a stream's hand-off): steps of
# 0.1 ms to 20 ms, fine enough that a percentile read off the counts can
# be laid beside one taken on a client's clock, then doubling to 20.48 s.
# Bucket i holds [edges[i-1], edges[i]); one more above the last edge.
GAP_EDGES_MS = tuple(round(0.1 * i, 1) for i in range(1, 201)) + tuple(
    20.0 * 2 ** i for i in range(1, 11))
# What the loop did between two tokens of one request, first match wins:
# the request was preempted and recomputed; a prefill step (a whole
# prompt or a chunk, of any request) was read; the step that sampled the
# token was launched with nothing unread ahead of it (the loop had
# stopped overlapping, for one of DRAIN_REASONS, or had paused); none of
# these, two decode steps back to back.
GAP_CAUSES = ("after_preempt", "after_prefill", "after_drain", "decode")
# `serve_llm_itl_ms`'s boundaries: the same gaps for an operator
ITL_BOUNDS_MS = (0.5, 1, 2, 3, 4, 5, 6, 8, 10, 15, 25, 50, 100, 250, 1000)
SLOW_TURN_MS = 250.0  # ten times the longest sound turn of any cell
# the engines alive in this process (a replica has one), for code that
# runs beside an engine and was handed its weights alone
_ENGINES: "weakref.WeakSet[LLMEngine]" = weakref.WeakSet()


def engines() -> "list[LLMEngine]":
    return list(_ENGINES)


def _added(pick, finish=sum):
    """A reader for one series of the metrics page (`collect=`), run on
    the scraping thread under the locks `stats()` takes and no other:
    `pick(engine)` -> a number or a ratio's parts, or those in dicts by the
    tags after the model's; `finish` makes one number of what the engines
    of one model and tag values hold (`of`: those alive)."""
    def read(of=None) -> dict:
        parts: dict[tuple, list] = {}
        for eng in engines() if of is None else of:
            for key, v in _leaves(pick(eng), (eng.config.model,)):
                parts.setdefault(key, []).append(v)
        return {key: float(finish(vs)) for key, vs in parts.items()}
    return read


def _leaves(v, key: tuple) -> list:
    if not isinstance(v, dict):
        return [(key, v)]
    return [kv for k, sub in v.items() for kv in _leaves(sub, (*key, k))]


def _ratio(parts: list) -> float:
    """Of (numerator, denominator) an engine: of the two added."""
    return sum(n for n, _ in parts) / (sum(d for _, d in parts) or 1)


def _imbalance(parts: list) -> float:
    """Of pairs by expert an engine: the most loaded over the mean, added."""
    per = np.zeros(max(len(p) for p in parts), np.int64)
    for p in parts:
        per[:len(p)] += p
    return per.max() / per.mean()


def gap_bucket(ms: float) -> int:
    """The bucket of GAP_EDGES_MS that a duration of `ms` falls in."""
    return bisect.bisect_right(GAP_EDGES_MS, ms)


class _Durations:
    """Counts of durations on GAP_EDGES_MS with their sum and their
    largest: one writer, readers copy."""

    __slots__ = ("counts", "sum_ms", "max_ms")

    def __init__(self):
        self.counts = [0] * (len(GAP_EDGES_MS) + 1)
        self.sum_ms = 0.0
        self.max_ms = 0.0

    def add(self, ms: float) -> None:
        self.counts[gap_bucket(ms)] += 1
        self.sum_ms += ms
        if ms > self.max_ms:
            self.max_ms = ms


class StreamAccount:
    """The replica's half of every stream's hand-off, summed over the
    engine's streams, under the lock. A pulled stream folds its own
    counts in every 64 items and at its end (`RequestStream`); pushed
    items are folded a message, by the flush that sends it (`shipping`)."""

    def __init__(self):
        self._lock = threading.Lock()
        self.items = 0
        self.pickup_s = 0.0
        self.ship_s = 0.0
        self.max_ms = 0.0
        self.handoff = [0] * (len(GAP_EDGES_MS) + 1)
        self.pushed = 0    # items that left through the shipper
        self.messages = 0  # `stream_items` messages they left in

    def fold(self, items: int, pickup_s: float, ship_s: float,
             max_ms: float, buckets: dict, messages: int = 0) -> None:
        """`messages`: the pushed messages the items left in, none for a
        pulled stream's."""
        with self._lock:
            if messages:
                self.pushed += items
                self.messages += messages
            self.items += items
            self.pickup_s += pickup_s
            self.ship_s += ship_s
            self.max_ms = max(self.max_ms, max_ms)
            for i, n in buckets.items():
                self.handoff[i] += n

    def shipping(self, put_times: list) -> "_Shipping":
        """What a `StreamWriter.put` names as its observer: the context
        a flush holds over one message, from taking its items up (each
        put down at its `put_times` reading) to handing the message to
        the socket."""
        return _Shipping(self, put_times)

    def stats(self) -> dict:
        with self._lock:
            return {"items": self.items, "pickup_s": self.pickup_s,
                    "ship_s": self.ship_s, "edges_ms": list(GAP_EDGES_MS),
                    "handoff": list(self.handoff), "max_ms": self.max_ms,
                    "pushed": self.pushed, "messages": self.messages}


class _Shipping:
    """One pushed message in the account, and one `llm.stream.ship`
    event in a device profile, on the flushing thread's line (the
    loop's, inside `llm.emit`)."""

    __slots__ = ("account", "put_times", "taken", "event")

    def __init__(self, account: StreamAccount, put_times: list):
        self.account = account
        self.put_times = put_times

    def __enter__(self):
        self.event = tracing.annotate("llm.stream.ship")
        self.event.__enter__()
        self.taken = time.perf_counter()

    def __exit__(self, *exc):
        sent = time.perf_counter()
        self.event.__exit__(*exc)
        ship = sent - self.taken
        pickup, longest, buckets = 0.0, 0.0, {}
        for put_down in self.put_times:
            pickup += self.taken - put_down
            ms = (sent - put_down) * 1e3
            longest = max(longest, ms)
            i = gap_bucket(ms)
            buckets[i] = buckets.get(i, 0) + 1
        n = len(self.put_times)
        self.account.fold(n, pickup, ship * n, longest, buckets, messages=1)


@dataclasses.dataclass
class _Flight:
    """One step from its planning to its commit."""

    kind: str  # "prefill" | "decode"
    work: PrefillWork | DecodeWork
    # the lanes it samples a token for (none: an intermediate chunk),
    # and how many tokens of each the host had not read at its launch
    sampled: list[Sequence]
    unread: list[int]
    ver: int  # the weight version its program runs on
    ahead: bool  # planned while the step before it was unread
    t0: float  # perf_counter at its planning
    handle: Launched | None = None
    error: Exception | None = None  # raised by its launch
    # under speculation: the lanes of `sampled` in the plain program,
    # and those with a draft, verified once the plain ones are committed
    plain: list[Sequence] = dataclasses.field(default_factory=list)
    drafted: list[tuple[Sequence, list[int]]] = dataclasses.field(
        default_factory=list)


class RequestStream:
    """Iterator over one request's token events.

    Yields ``{"token": id, "index": n}`` dicts as tokens are produced,
    then raises StopIteration; `final()` returns the summary event
    (token_ids, finish_reason, counts) once the stream is drained.

    It times its own half of the hand-off to the client, on the
    consumer's thread: an item's **pickup** (the loop put it down ->
    the consumer took it up: the consumer's wake) and its **ship** (the
    consumer was handed it -> it asked for the next: what it spent
    serialising and sending it; also an `llm.stream.ship` event in a
    device profile). Kept on the stream without a lock and folded into
    `account`, where there is one, every FOLD_EVERY items and at the
    stream's end.

    A stream made with a `writer` (a `core.stream_push.StreamWriter`:
    the request came through a replica's worker) is PUSHED instead:
    the loop puts each event, and the final one, on the writer, nobody
    iterates, and the loop's one flush after a step's emit sends the
    events of every lane in one message and accounts for them
    (`StreamAccount.shipping`: pickup = put down -> the flush took it
    up, ship = taken up -> its message handed to the socket).
    `tokens=False` pushes the final event alone."""

    FOLD_EVERY = 64

    def __init__(self, seq_id: int, account: StreamAccount | None = None,
                 writer=None, tokens: bool = True):
        self.seq_id = seq_id
        self._writer = writer
        self._tokens = tokens
        self._q: "queue.Queue[Any]" = queue.Queue()
        self._final: dict | None = None
        self._ended = False  # sentinel consumed (iteration or next_event)
        self._account = account
        # the item handed out and not yet asked beyond: when it was
        # handed out, its pickup, and the profile's event over its ship
        self._out: tuple | None = None
        # not yet folded: items, pickup and ship seconds, the longest
        # hand-off in ms, hand-offs by bucket of GAP_EDGES_MS
        self._n = 0
        self._pickup_s = 0.0
        self._ship_s = 0.0
        self._max_ms = 0.0
        self._buckets: dict[int, int] = {}

    # engine side -----------------------------------------------------
    def _emit(self, ev: dict) -> None:
        if self._writer is None:
            # the time it was put down travels beside the event, not in it
            self._q.put((ev, time.perf_counter()))
        elif self._tokens:
            self._writer.put(ev, self._account)

    def _close(self, final: dict) -> None:
        self._final = final
        if self._writer is None:
            self._q.put(_FINAL)
        else:  # the summary is the stream's last item, and no token
            self._writer.put(final)
            self._writer.close()

    # consumer side ---------------------------------------------------
    def __iter__(self):
        return self

    def __next__(self):
        ev = self.next_event()
        if ev is None:
            raise StopIteration
        return ev

    def next_event(self, timeout: float | None = None):
        """Blocking fetch; returns None at end-of-stream (persistently —
        mixing with iteration is safe) and raises TimeoutError if no
        event arrives within `timeout` seconds."""
        if self._ended:
            return None
        out = self._out
        if out is not None:  # asked for the next: the last is sent
            now = time.perf_counter()
            self._out = None
            handed, pickup, ship = out
            ship.__exit__(None, None, None)
            self._n += 1
            self._pickup_s += pickup
            self._ship_s += now - handed
            ms = (pickup + now - handed) * 1e3
            if ms > self._max_ms:
                self._max_ms = ms
            i = gap_bucket(ms)
            self._buckets[i] = self._buckets.get(i, 0) + 1
            if self._n >= self.FOLD_EVERY:
                self._fold()
        try:
            item = self._q.get(timeout=timeout)
        except queue.Empty:
            raise TimeoutError(
                f"no token event within {timeout}s") from None
        if item is _FINAL:
            self._ended = True
            self._fold()
            return None
        ev, put_down = item
        ship = tracing.annotate("llm.stream.ship")
        ship.__enter__()
        now = time.perf_counter()
        self._out = (now, now - put_down, ship)
        return ev

    def _fold(self) -> None:
        if self._account is not None and self._n:
            self._account.fold(self._n, self._pickup_s, self._ship_s,
                               self._max_ms, self._buckets)
        self._n, self._pickup_s, self._ship_s = 0, 0.0, 0.0
        self._max_ms, self._buckets = 0.0, {}

    def final(self) -> dict | None:
        return self._final


class LLMEngine:
    """Continuous-batching engine for one model instance."""

    def __init__(self, config: EngineConfig, *, params: Any = None,
                 mesh=None):
        import jax

        # before the first compile, so that start-up's share is counted
        tracing.watch_compiles()
        self.config = config
        reg = adapters()
        if config.model not in reg:
            raise ValueError(
                f"unknown model {config.model!r}; have {sorted(reg)}")
        adapter = reg[config.model]
        if config.model_config is not None \
                and not isinstance(config.model_config, dict):
            cfg = config.model_config
        else:
            try:
                cfg = adapter.presets[config.preset]()
            except KeyError:
                raise ValueError(
                    f"unknown preset {config.preset!r} for "
                    f"{config.model}; have {sorted(adapter.presets)}")
            if config.model_config:  # a dict: fields laid over the preset
                cfg = dataclasses.replace(cfg, **config.model_config)
        self.model_cfg = cfg
        max_len = config.max_model_len or cfg.block_size
        if max_len > cfg.block_size:
            raise ValueError(
                f"max_model_len {max_len} exceeds the model's positional "
                f"range {cfg.block_size}")

        # where a replica's start goes, host clock, seconds (stats())
        self._startup = dict.fromkeys(
            ("init_params", "build_runner", "init_compile", "warmup",
             "warmup_trace", "warmup_lower", "warmup_compile"), 0.0)
        self._warmup_cache = {"hits": 0, "misses": 0}
        # what making the weights and building the runner compiled, or
        # loaded from the persistent cache: `init_compile` its seconds
        compiled_before = tracing.compile_totals()
        if params is None:
            t0 = time.perf_counter()
            params = jax.block_until_ready(
                adapter.init_fn(jax.random.PRNGKey(config.seed), cfg))
            self._startup["init_params"] = time.perf_counter() - t0

        # what the family caches beside K and V, read off the adapter: a
        # recurrent state a lane slot, or nothing
        state_layout = state_layout_of(adapter, cfg, config.max_batch_size)
        # the kinds of layer that have keys and values, read off the
        # adapter too: a pool and a block table each
        kinds = adapter.kv_kinds(cfg)
        windowed = any(kind.window is not None for kind in kinds)
        spec_cfg = config.speculative
        if spec_cfg and state_layout is not None:
            raise ValueError(
                f"speculative decoding is not available for "
                f"{config.model!r}: the family carries recurrent state, "
                f"and a rejected draft would have to roll it back "
                f"(ROADMAP.md, recurrent state snapshots)")
        if spec_cfg and windowed:
            raise ValueError(
                f"speculative decoding is not available for "
                f"{config.model!r}: the family has window attention "
                f"layers, whose pages are given back behind the window as "
                f"rows are planned, before a draft is accepted or "
                f"rejected (ROADMAP.md, speculation with a window kind)")
        if spec_cfg and any(kind.latent for kind in kinds):
            raise ValueError(
                f"speculative decoding is not available for "
                f"{config.model!r}: the family's rows choose the slots "
                f"they attend to among the cached rows and the program's "
                f"own (or, without an indexer, no verify program over a "
                f"latent row has been written), and the verify program "
                f"stores a drafted run's rows before it knows which of "
                f"them stay (ROADMAP.md, speculation with a latent kind)")
        chunking = config.prefill_chunk_size > 0
        rows = chunk_rows(config.prefill_chunk_size, config.block_size,
                          max_len) if chunking else 0
        num_blocks = config.num_blocks
        if num_blocks is None:
            num_blocks = auto_num_blocks(
                kinds=kinds,
                block_size=config.block_size,
                dtype_bytes=jax.numpy.dtype(cfg.dtype).itemsize,
                max_model_len=max_len,
                max_batch_size=config.max_batch_size,
                chunk_rows=rows,
                memory_fraction=config.memory_fraction,
                tensor_ways=(dict(mesh.shape).get("tensor", 1)
                             if mesh is not None else 1),
                state_bytes=(state_layout.nbytes if state_layout else 0),
            )
        # a size a kind: `num_blocks` is the first kind's pool, or one a
        # kind; a window kind's is sized off the lanes otherwise
        sizes = blocks_by_kind(kinds, num_blocks, config.block_size,
                               rows or max_len, config.max_batch_size)
        max_blocks_per_seq = (max_len + config.block_size - 1) \
            // config.block_size
        for kind, n in zip(kinds, sizes):
            need = max_blocks_per_seq if kind.window is None else \
                KVLayout.of(kind, 0, config.block_size).lane_pages(
                    rows or max_len)
            if n - 1 < need:
                raise ValueError(
                    f"pool of {n} blocks cannot hold one "
                    f"max_model_len={max_len} sequence "
                    f"({need} blocks of the {kind.name} kind needed); "
                    f"raise num_blocks or lower max_model_len")

        # prefix reuse needs the prefill-from-offset (chunk) program:
        # with chunking disabled the pool runs as a plain allocator. And
        # a prefix hit hands over pages of K and V but no recurrent
        # state: for a family that has it no prefix is looked up (each
        # admission that would have is counted, `stats()["state"]`). Nor
        # for a family with a window kind, whose pages before a matched
        # boundary are gone (counted too, `stats()["kv"]`)
        prefix = config.enable_prefix_cache and chunking
        self.state_slots = StateSlots(state_layout, prefix_declined=prefix) \
            if state_layout is not None else None
        self.kv = KVPools(
            kinds,
            [BlockPool(n, config.block_size, enable_prefix_cache=(
                prefix and state_layout is None and not windowed
                and i == 0)) for i, n in enumerate(sizes)],
            prefix_declined=prefix and windowed)
        self.pool = self.kv.pools[0]  # the one the prefix index addresses
        # speculative decoding: proposer on the host, verify program on
        # the device; greedy outputs stay bit-identical to spec-off
        from ray_tpu.serve.llm.spec import build_proposer

        self._proposer = build_proposer(spec_cfg) if spec_cfg else None
        self._spec_k = spec_cfg.num_draft_tokens if spec_cfg else 0
        t0 = time.perf_counter()
        self.runner = ModelRunner(
            adapter, cfg, params,
            block_size=config.block_size,
            num_blocks=sizes,
            max_model_len=max_len,
            max_batch_size=config.max_batch_size,
            prefill_bucket_min=config.prefill_bucket_min,
            prefill_chunk_size=(config.prefill_chunk_size if chunking
                                else None),
            mesh=mesh,
            sample_seed=config.seed + 1,
            num_draft_tokens=self._spec_k,
        )
        # weights cast to their resident dtypes and placed, both pools
        # and the lanes' state allocated. Nothing keeps the tree as given
        # (float32 for GPT-2) beyond this constructor: it is freed before
        # warm-up
        jax.block_until_ready((self.runner.params, self.runner.k_pages,
                               self.runner.v_pages, self.runner.state))
        self._startup["build_runner"] = time.perf_counter() - t0
        spent = tracing.compile_totals(since=compiled_before)
        self._startup["init_compile"] = spent["backend_compile"]
        self._init_cache = {k: int(spent[k])
                            for k in ("programs", "hits", "misses")}
        # seconds by phase of the step loop; by step kind the steps, the
        # bytes fetched to the host and the routed experts' account; tokens
        # generated and prefill programs committed: plain numbers, written
        # by the one thread that steps (under _step_lock), copied by stats()
        self.phases = self.runner.phases
        self._steps = {"decode": 0, "prefill": 0}
        self._d2h = {"decode": 0, "prefill": 0}
        self._tokens = self._chunks = 0
        self._moe: dict[str, dict] = {}
        self._spec_proposed_total = self._spec_accepted_total = 0
        # steps launched and not yet read, oldest first: at most one
        # between two calls of step(), two inside one (the one being
        # read and the one behind it). Touched under _step_lock only
        self._flights: collections.deque[_Flight] = collections.deque()
        self._last_collect = 0.0  # perf_counter at the last step's end
        # every gap between two tokens of one request, by what the loop
        # did in it (GAP_CAUSES), and the tokens that had none: a verify
        # dispatch's after its first. `_prefill_reads`: prefill steps
        # read so far, which a sequence remembers at each of its tokens
        self._gaps = {cause: _Durations() for cause in GAP_CAUSES}
        self._burst_tokens = 0
        # a writer some lane of the step being read pushed an event to:
        # the step's events are flushed through it once, after its emit
        self._pushed_to = None
        self._prefill_reads = 0
        # the same gaps on `serve_llm_itl_ms`'s boundaries, since the
        # last step's bookkeeping: {cause: [{bucket: n}, their sum in ms]}
        self._itl_pending: dict[str, list] = {}
        # the loop's own wall clock (`perf_counter` at the first call of
        # step()), and its turns by the kind of step they read: how
        # long each took, and the seconds of those over SLOW_TURN_MS
        self._loop_t0: float | None = None
        self._turns = {kind: _Durations() for kind in ("decode", "prefill")}
        self._slow_turn_s = dict.fromkeys(self._turns, 0.0)
        self._stream_account = StreamAccount()
        # how often the next step was on the device before this one's
        # results were read, and why not when it was not (stats())
        self._overlap = {
            "launched_ahead": {"decode": 0, "prefill": 0},
            "launched_drained": {"decode": 0, "prefill": 0},
            "drains": dict.fromkeys(DRAIN_REASONS, 0),
            "discarded_tokens": 0}
        self.scheduler = Scheduler(
            self.kv, max_batch_size=config.max_batch_size,
            max_model_len=max_len,
            # the runner rounds the chunk to a page-aligned size; reuse
            # its value so scheduler chunks match the compiled buckets
            chunk_size=(self.runner.prefill_chunk_size or 0),
            spec_tokens=self._spec_k)
        # the router's experts this replica holds, for the routing account
        self._held = (adapter.held_experts(cfg)
                      if adapter.held_experts is not None else None)

        self._ids = itertools.count()
        self._streams: dict[int, RequestStream] = {}  # guarded_by(_lock)
        self._lock = threading.Lock()
        self._step_lock = threading.Lock()
        # callers waiting for _step_lock that are not the loop (a swap,
        # an abort): the loop lets them in before its next turn, which a
        # plain lock released and taken again at once never does
        self._urgent = 0  # guarded_by(_lock)
        # weight hot-swap state: bumped only by update_weights(), which
        # holds _step_lock — so within one step() every sampled token
        # sees ONE version (no mid-decode-step version mix)
        self._weight_version = 0  # guarded_by(_step_lock)
        # cumulative per-phase seconds over finished requests — the
        # llm_status()/engine_stats() aggregate of the waterfall
        self._phase_totals: dict[str, float] = {}  # guarded_by(_lock)
        self._finished_requests = 0  # guarded_by(_lock)
        self._outcomes: dict[str, int] = {}  # guarded_by(_lock)
        self._build_metrics()
        _ENGINES.add(self)  # built: the page reads it from here on

    # ----------------------------------------------------------- metrics

    def _build_metrics(self):
        """The engine's series of the metrics page. A counter or a gauge
        is a view (`_added`): asked for the page, it reads off the engines
        alive a number `stats()` returns; only a histogram is written to,
        where its observations happen. Every engine calls this, and a
        registry keeps the first metric of a name."""
        from ray_tpu.util.metrics import Counter, Gauge, Histogram

        def depth(key):
            return _added(lambda e: e.scheduler.depth()[key])

        def pools(key, finish=sum):  # by kind of KV layer
            return _added(lambda e: {kind: pool[key] for kind, pool
                                     in e.kv.stats().items()}, finish)

        def state(key):  # of a family with recurrent state, or 0
            return _added(lambda e: e.state_slots.stats()[key]
                          if e.state_slots else 0)

        def routed(key, finish=sum):  # by step kind
            return _added(lambda e: {kind: acc[key] for kind, acc
                                     in list(e._moe.items())}, finish)

        tags, by_kind = ("model",), ("model", "kind")
        self._tags = {"model": self.config.model}
        self._m_ttft = Histogram(
            "serve_llm_ttft_ms", "Time to first token",
            boundaries=(1, 5, 10, 25, 50, 100, 250, 500, 1000, 5000),
            tag_keys=tags)
        self._m_itl = Histogram(
            "serve_llm_itl_ms",
            "Gap between two streamed tokens of one request, where the "
            "loop emits them, by what the loop did in it: after_preempt "
            "(the request was recomputed), after_prefill (a prefill "
            "step of any request was read between), after_drain (the "
            "sampling step was launched with none unread ahead of it), "
            "decode (two decode steps back to back)",
            boundaries=ITL_BOUNDS_MS, tag_keys=("model", "cause"))
        self._itl_tags = {cause: {"model": self.config.model, "cause": cause}
                          for cause in GAP_CAUSES}
        self._m_step = Histogram(
            "serve_llm_step_ms", "Engine step latency",
            boundaries=(1, 2, 3, 4, 5, 6, 8, 10, 12, 15, 20, 25, 50, 100,
                        250, 500, 1000), tag_keys=by_kind)
        self._step_tags = {kind: {"model": self.config.model, "kind": kind}
                           for kind in self._steps}
        self._m_swap_s = Histogram(
            "rl_weight_swap_seconds",
            "Wall time of a drain-free weight hot-swap (params install "
            "+ prefix-cache invalidation), streams in flight",
            boundaries=(0.001, 0.005, 0.01, 0.05, 0.1, 0.5, 1, 5, 10),
            tag_keys=tags)
        # SLO attribution plane (direction 2's autoscaler input): TTFT
        # decomposed into its queue and prefill components, and TPOT
        # (decode seconds per generated token after the first)
        self._m_slo_ttft = Histogram(
            "serve_slo_ttft_ms",
            "Time to first token, decomposed: phase=queue (admission "
            "wait), phase=prefill (prefix match + prefill work), "
            "phase=total",
            boundaries=(1, 5, 10, 25, 50, 100, 250, 500, 1000, 5000),
            tag_keys=("model", "phase"))
        self._m_slo_tpot = Histogram(
            "serve_slo_tpot_ms",
            "Time per output token after the first (decode + verify "
            "phase seconds / tokens committed after the first — "
            "speculative steps commit several tokens per dispatch, so "
            "per-step time is divided over tokens actually committed)",
            boundaries=(0.5, 1, 2.5, 5, 10, 25, 50, 100, 250, 500),
            tag_keys=tags)
        self._m_verify_ms = Histogram(
            "serve_llm_verify_step_ms",
            "Speculative verify dispatch latency (one drafted run)",
            boundaries=(1, 5, 10, 25, 50, 100, 250, 500, 1000), tag_keys=tags)
        views = [
            Counter("serve_llm_tokens_generated_total",
                    "Tokens generated by this engine", tag_keys=tags,
                    collect=_added(lambda e: e._tokens)),
            Counter("serve_llm_requests_total",
                    "Requests finished, by outcome",
                    tag_keys=("model", "outcome"),
                    collect=_added(lambda e: dict(e._outcomes))),
            Counter("serve_llm_preemptions_total",
                    "Sequences preempted on cache exhaustion", tag_keys=tags,
                    collect=depth("preemptions")),
            Gauge("serve_llm_queue_depth", "Waiting requests", tag_keys=tags,
                  collect=depth("waiting")),
            Gauge("serve_llm_running", "Sequences in the decode set",
                  tag_keys=tags, collect=depth("running")),
            Gauge("serve_llm_cache_utilization",
                  "KV pool pages in use / usable pages", tag_keys=tags,
                  collect=_added(lambda e: (e.pool.num_used(),
                                            e.pool.usable_blocks), _ratio)),
            Counter("serve_llm_prefix_cache_hits_total",
                    "KV pages served from the prefix cache at admission",
                    tag_keys=tags, collect=depth("prefix_hit_pages")),
            Counter("serve_llm_prefix_cache_misses_total",
                    "KV pages that had to be prefilled at admission",
                    tag_keys=tags, collect=depth("prefix_miss_pages")),
            Counter("serve_llm_prefix_cache_evictions_total",
                    "Cached refcount-0 pages evicted for reuse", tag_keys=tags,
                    collect=depth("prefix_evictions")),
            Gauge("serve_llm_prefix_cached_blocks",
                  "Refcount-0 pages retained for prefix reuse", tag_keys=tags,
                  collect=depth("blocks_cached")),
            Counter("serve_llm_prefill_chunks_total",
                    "Prefill chunks executed", tag_keys=tags,
                    collect=_added(lambda e: e._chunks)),
            # every install but the constructor's
            Counter("serve_llm_weight_swaps_total",
                    "Weight hot-swaps installed at a step boundary",
                    tag_keys=tags,
                    collect=_added(
                        lambda e: e.runner.weights["installs"] - 1)),
            # speculative decoding plane: proposed = draft tokens sent to
            # verify; accepted + rejected = proposed (watchtower's
            # spec-accept-collapse rule reads the accepted:rejected ratio)
            Counter("serve_llm_spec_proposed_total",
                    "Draft tokens proposed to the verify program",
                    tag_keys=tags,
                    collect=_added(lambda e: e._spec_proposed_total)),
            Counter("serve_llm_spec_accepted_total",
                    "Draft tokens accepted by the verify program",
                    tag_keys=tags,
                    collect=_added(lambda e: e._spec_accepted_total)),
            Counter("serve_llm_spec_rejected_total",
                    "Draft tokens rejected by the verify program",
                    tag_keys=tags,
                    collect=_added(lambda e: e._spec_proposed_total
                                   - e._spec_accepted_total)),
            Gauge("serve_llm_spec_accept_ratio",
                  "Cumulative draft acceptance ratio (accepted / proposed)",
                  tag_keys=tags,
                  collect=_added(lambda e: (e._spec_accepted_total,
                                            e._spec_proposed_total), _ratio)),
            Gauge("serve_llm_weight_bytes",
                  "Bytes of the resident parameter tree, each leaf in the "
                  "dtype the programs consume it in", tag_keys=tags,
                  collect=_added(
                      lambda e: e.runner.weights["resident_bytes"])),
            Gauge("serve_llm_weight_cast_leaves",
                  "Leaves the last weight install had to convert to their "
                  "resident dtype (0: the tree arrived as it is held)",
                  tag_keys=tags,
                  collect=_added(lambda e: e.runner.weights["cast_leaves"])),
            Counter("serve_llm_d2h_bytes_total",
                    "Bytes of device results fetched to the host by engine "
                    "steps (sampled tokens and logits), by step kind",
                    tag_keys=by_kind, collect=_added(lambda e: e._d2h)),
            Counter("serve_llm_ctx_slots_total",
                    "Slots of cached context by kind of program: read as "
                    "launched (whole tiles, to the longest lane of a group), "
                    "valid (below a lane's length), full (every row to "
                    "max_model_len); of a latent kind also scored (indexer "
                    "keys read) and selected (slots a row can attend after "
                    "its indexer's choice)",
                    tag_keys=("model", "kind", "what"),
                    collect=_added(lambda e: e.runner.context_slots)),
            # routed experts: what the programs report of their routing, by
            # step kind (a dense model's programs report nothing)
            Counter("serve_llm_moe_pairs_total",
                    "(token, expert) pairs the routed-expert layers "
                    "computed, padded rows included, by step kind",
                    tag_keys=by_kind, collect=routed("pairs")),
            Counter("serve_llm_moe_experts_touched_total",
                    "Experts that received at least one pair, summed over "
                    "programs and layers, by step kind", tag_keys=by_kind,
                    collect=routed("experts_touched")),
            Counter("serve_llm_moe_layer_calls_total",
                    "Routed-expert layers run (programs x layers), by step "
                    "kind", tag_keys=by_kind, collect=routed("layer_calls")),
            Gauge("serve_llm_moe_load_imbalance",
                  "Pairs of the most loaded expert over the mean expert's, "
                  "cumulative, by step kind", tag_keys=by_kind,
                  collect=routed("expert_pairs", _imbalance)),
            Counter("serve_llm_steps_launched_total",
                    "Step programs enqueued, by step kind and by whether the "
                    "step before them was still unread (ahead=1) or the "
                    "engine had read everything (ahead=0)",
                    tag_keys=("model", "kind", "ahead"),
                    collect=_added(lambda e: {kind: {
                        "1": n, "0": e._overlap["launched_drained"][kind]}
                        for kind, n in e._overlap["launched_ahead"].items()})),
            Counter("serve_llm_step_drains_total",
                    "Steps read with none launched behind them, by what kept "
                    "the next one from being planned",
                    tag_keys=("model", "reason"),
                    collect=_added(lambda e: e._overlap["drains"])),
            Counter("serve_llm_discarded_tokens_total",
                    "Sampled ids dropped at commit: their lane had ended (an "
                    "eos in the step before, an abort) while the program ran",
                    tag_keys=tags,
                    collect=_added(lambda e: e._overlap["discarded_tokens"])),
            # recurrent state (a family that has it): what the lanes' slots
            # hold, and the two things it changes for a request
            Gauge("serve_llm_state_bytes",
                  "Bytes of recurrent state held for the lane slots, all "
                  "layers and parts (0: the family has none)", tag_keys=tags,
                  collect=state("bytes")),
            Counter("serve_llm_state_resets_total",
                    "Lane slots started from zero by a program that ran a "
                    "sequence's first rows (admissions, recomputes included)",
                    tag_keys=tags, collect=state("resets")),
            Counter("serve_llm_state_carried_total",
                    "Prefill programs that started from the recurrent state "
                    "an earlier chunk of their sequence left in the lane's "
                    "slot", tag_keys=tags, collect=state("carried")),
            Counter("serve_llm_state_decode_lanes_total",
                    "Lane slots whose recurrent state a decode program moved "
                    "one step on (the slots its lanes owned), summed over "
                    "the steps", tag_keys=tags, collect=state("decode_lanes")),
            # KV pages by kind of layer (one kind for most families)
            Gauge("serve_llm_kv_pages_used",
                  "Pages of a kind's pool held by sequences", tag_keys=by_kind,
                  collect=pools("pages_used")),
            Gauge("serve_llm_kv_pages_free",
                  "Pages of a kind's pool that can be allocated",
                  tag_keys=by_kind, collect=pools("pages_free")),
            Gauge("serve_llm_kv_largest_table",
                  "Most pages of a kind one sequence has held at once",
                  tag_keys=by_kind, collect=pools("largest_table", max)),
            Counter("serve_llm_kv_released_total",
                    "Pages given back behind the window while their sequence "
                    "ran (a window kind only)", tag_keys=by_kind,
                    collect=pools("released_behind_window")),
            Counter("serve_llm_kv_prefix_total",
                    "Admissions by what became of their prefix lookup: taken "
                    "(a match of at least a page) or declined (none looked "
                    "up: the family has a window kind)",
                    tag_keys=("model", "outcome"),
                    collect=_added(lambda e: {
                        "taken": e.kv.prefix_taken,
                        "declined": e.kv.prefix_declines})),
            Counter("serve_llm_kv_rows_written_total",
                    "Valid rows of K (and as many of V) stored in a kind's "
                    "pools, by path: paged (a prompt's or a chunk's program, "
                    "a page at a time) or rowwise (decode, verify, a bucket "
                    "that is not whole pages)",
                    tag_keys=("model", "kind", "path"),
                    collect=_added(lambda e: e.runner.rows_written)),
        ]
        self._counters = [m for m in views if m.TYPE == "counter"]

    def __del__(self, going=sys.is_finalizing):
        # what an engine counted stays in its counters when it goes (not
        # when the interpreter does): none goes down inside a process
        if not going():
            for counter in self.__dict__.get("_counters", ()):
                for key, n in counter.collect([self]).items():
                    counter.inc(n, tags=dict(zip(counter.tag_keys, key)))

    # ------------------------------------------------------------ intake

    def add_request(self, prompt: Seq[int],
                    sampling: SamplingParams | None = None,
                    *, writer=None, tokens: bool = True
                    ) -> RequestStream:
        """`writer`: push the stream's events there instead of keeping
        them for an iterator (`RequestStream`)."""
        sampling = sampling or SamplingParams()
        prompt = [int(t) for t in prompt]
        if not prompt:
            raise ValueError("empty prompt")
        seq = Sequence(seq_id=next(self._ids), prompt=prompt,
                       sampling=sampling)
        # the request's trace context: a child of whatever span chain
        # submitted it (handle call, proxy request), so the finalize-
        # time waterfall spans correlate by trace_id
        from ray_tpu.utils.events import child_trace

        seq.trace = child_trace(tracing.current_trace())
        stream = RequestStream(seq.seq_id, self._stream_account, writer,
                               tokens)
        with self._lock:
            # validate (scheduler.add raises on over-long prompts) BEFORE
            # registering the stream, or rejected requests leak entries
            self.scheduler.add(seq)
            self._streams[seq.seq_id] = stream
        return stream

    def generate(self, prompt: Seq[int],
                 sampling: SamplingParams | None = None,
                 *, drive: bool = False, timeout: float = 120.0) -> dict:
        """Blocking convenience: returns the final event. With
        ``drive=True`` the caller's thread steps the engine itself
        (tests, bench — no loop thread needed)."""
        stream = self.add_request(prompt, sampling)
        deadline = time.monotonic() + timeout
        if drive:
            while stream.final() is None:
                if not self.step():
                    time.sleep(0.001)
                if time.monotonic() > deadline:
                    raise TimeoutError("generate() timed out")
            for _ in stream:
                pass
            return stream.final()
        while True:
            ev = stream.next_event(
                timeout=max(0.01, deadline - time.monotonic()))
            if ev is None:  # end of stream
                return stream.final()
            if time.monotonic() > deadline:
                raise TimeoutError("generate() timed out")

    # -------------------------------------------------------------- step

    def step(self) -> bool:
        """One turn of the loop: plan and launch the step behind the one
        in flight, then read that one's results, commit and emit them.
        Each call reads the results of exactly one step. Returns False
        only when nothing was in flight and there was nothing to do.
        Serialized: concurrent callers queue behind `_step_lock` (the
        deployment runs a single loop thread; tests may drive from
        several)."""
        t_in = time.perf_counter()
        if self._loop_t0 is None:
            self._loop_t0 = t_in
        if self._urgent or not self._step_lock.acquire(blocking=False):
            with self.phases.phase("yield"):
                while self._urgent:  # a swap or an abort wants the lock
                    time.sleep(0.0002)
                self._step_lock.acquire()
        try:
            planned = []
            with self.phases.phase("schedule"):
                if not self._flights:
                    first, closed = self._plan(ahead=False)
                    if first is None:
                        return closed  # nothing to run
                    planned.append(first)
                if self._proposer is not None:
                    # drafts are read from the tokens this step commits
                    self._overlap["drains"]["speculation"] += 1
                else:
                    behind, _ = self._plan(ahead=True)
                    if behind is not None:
                        planned.append(behind)
            self._turn(planned, t_in)
            return True
        finally:
            self._step_lock.release()

    def _plan(self, ahead: bool) -> "tuple[_Flight | None, bool]":
        """One scheduler decision, inside step()'s `schedule` phase.
        `ahead`: a step is in flight and its results are unread, so
        every one of its lanes is taken to continue. Returns the step to
        launch, if there is one, and whether `schedule()` itself closed
        a sequence out."""
        t0 = time.perf_counter()
        with self._lock:
            try:
                # may preempt lanes, unless their tokens are in flight
                work = self.scheduler.schedule(may_preempt=not ahead)
            except NeedsResults:
                self._overlap["drains"]["preempt"] += 1
                return None, False
            retired = self.scheduler.take_retired()
        for s in retired:  # schedule() closed these out itself
            self._finalize(s)
        if work is None:
            if ahead:
                self._overlap["drains"]["idle"] += 1
            return None, retired != []
        if isinstance(work, PrefillWork):
            kind = "prefill"
            sampled = [work.seq] if work.is_last else []
        else:
            kind, sampled = "decode", work.seqs
        # how many of a lane's tokens the host will not have read
        # when this step is launched
        unread = [s.inflight for s in sampled]
        for s in sampled:
            s.inflight += 1
        return _Flight(kind, work, sampled, unread, self._weight_version,
                       ahead, t0), retired != []

    def _turn(self, planned: "list[_Flight]", t_in: float) -> None:
        """Launch what was planned, then collect the oldest step in
        flight: one `llm.step.<kind>` interval, named after the step
        whose results it reads. The turn began at `t_in` (with the wait
        for the engine and the planning, in step()) and is written down
        by that kind."""
        oldest = self._flights[0] if self._flights else planned[0]
        with tracing.annotate("llm.step." + oldest.kind):
            for flight in planned:
                self._launch(flight)
            self._collect(self._flights.popleft())
        ms = (time.perf_counter() - t_in) * 1e3
        self._turns[oldest.kind].add(ms)
        if ms > SLOW_TURN_MS:
            self._slow_turn_s[oldest.kind] += ms / 1e3

    def _drain(self, reason: str | None) -> None:
        """Read every step in flight: for a caller that holds
        `_step_lock` and needs the engine between steps."""
        if self._flights and reason:
            self._overlap["drains"][reason] += 1
        while self._flights:
            self._turn([], time.perf_counter())

    def _launch(self, flight: "_Flight") -> None:
        """Enqueue a planned step's program; nothing is read."""
        which = "launched_ahead" if flight.ahead else "launched_drained"
        self._overlap[which][flight.kind] += 1
        try:
            if flight.kind == "prefill":
                flight.handle = self._launch_prefill(flight.work)
            else:
                self._launch_decode(flight)
        except Exception as e:  # noqa: BLE001
            flight.error = e  # its lanes are closed out at collect
        self._flights.append(flight)

    def _collect(self, flight: "_Flight") -> None:
        """Wait for a launched step, commit and emit what it sampled,
        write the step down, and let its device results go."""
        for s in flight.sampled:
            s.inflight -= 1
        if flight.kind == "prefill":
            self._prefill_reads += 1
        tokens, nxt, logits = 0, None, None
        try:
            if flight.error is not None:
                raise flight.error
            if flight.handle is not None:
                nxt, logits = self.runner.collect(flight.handle)
        except Exception as e:  # noqa: BLE001
            self._overlap["drains"]["error"] += 1
            work = flight.work
            lanes = [work.seq] if flight.kind == "prefill" else work.seqs
            lanes = [s for s in lanes if s.state is SeqState.RUNNING]
            with self._lock:
                for s in lanes:
                    self.scheduler.abort(s, f"error:{e!r}")
            for s in lanes:
                self._finalize(s)
        else:
            if flight.kind == "prefill":
                tokens = self._commit_prefill(flight, nxt, logits)
            else:
                if flight.handle is not None:
                    tokens = self._commit_decode(flight, nxt, logits)
                for s, d in flight.drafted:
                    tokens += self._verify_one(s, d, flight.ver)
        if self._pushed_to is not None:
            # the step's events of every pushed lane, one message an owner
            with self.phases.phase("emit"):
                self._pushed_to.flush()
            self._pushed_to = None
        with self.phases.phase("bookkeep"):
            # the wall time this step cost: from the end of the step
            # before it, or from its own planning after a pause
            now = time.perf_counter()
            step_ms = (now - max(flight.t0, self._last_collect)) * 1e3
            self._last_collect = now
            self._bookkeep(flight.kind, tokens, step_ms)
        with self.phases.phase("release"):
            # the last references to the program's results on the device
            # and to their copies on the host (a lane's logits row is
            # 200 KB at gpt2-large: freeing it is a system call)
            flight.handle = nxt = logits = None

    def _bookkeep(self, kind: str, tokens: int, step_ms: float) -> None:
        """What a step writes down once its results are out and nothing
        else keeps: its latency and the tokens' gaps (the histograms), and
        by its kind the step, the bytes it fetched and its routed experts."""
        self._m_step.observe(step_ms, tags=self._step_tags[kind])
        for cause, (buckets, total_ms) in self._itl_pending.items():
            self._m_itl.observe_buckets(buckets, total_ms,
                                        tags=self._itl_tags[cause])
        self._itl_pending.clear()
        self._tokens += tokens
        self._steps[kind] += 1
        self._d2h[kind] += self.runner.fetched_bytes
        self.runner.fetched_bytes = 0
        if self.runner.expert_pairs:  # never, for a dense model
            self._note_routing(kind, self.runner.take_expert_pairs())

    def _note_routing(self, kind: str, routed: list) -> None:
        """Account the step's routed-expert layers: `routed` holds one
        (L, n_experts) array of pairs per program the step ran."""
        c = np.stack(routed)  # (programs, L, n_experts)
        pairs, touched = int(c.sum()), int(np.count_nonzero(c))
        calls = c.shape[0] * c.shape[1]
        acc = self._moe.setdefault(kind, {
            "pairs": 0, "expert_pairs": np.zeros(c.shape[2], np.int64),
            "experts_touched": 0, "layer_calls": 0, "held_pairs": 0,
            "held_experts_touched": 0})
        acc["pairs"] += pairs
        # the pairs this replica's experts computed: all of them, unless
        # it holds a share of the router's experts
        held = (pairs, touched)
        if self._held is not None:
            here = c[:, :, self._held[0]:self._held[0] + self._held[1]]
            held = (int(here.sum()), int(np.count_nonzero(here)))
        acc["held_pairs"] += held[0]
        acc["held_experts_touched"] += held[1]
        acc["experts_touched"] += touched
        acc["layer_calls"] += calls
        acc["expert_pairs"] += c.sum(axis=(0, 1))

    def _launch_prefill(self, work: PrefillWork) -> Launched:
        """One prefill program: the whole prompt, or a chunk of it."""
        seq = work.seq
        sp = seq.sampling
        tokens = seq.refill_tokens[work.start:work.end]
        if self.state_slots is not None:
            if work.start == 0:
                # the program zeroes the slot; no prefix was looked up
                self.state_slots.resets += 1
            else:  # it starts from what the lane's last chunk left
                self.state_slots.carried += 1
        if work.start == 0 and work.is_last:
            # whole prompt in one go and nothing cached: the
            # monolithic program skips the context gather
            return self.runner.launch_prefill(
                tokens, work.tables, sp.temperature, sp.top_k, sp.top_p,
                seq.slot)
        return self.runner.launch_chunk(
            tokens, work.start, work.tables, sp.temperature, sp.top_k,
            sp.top_p, seq.slot)

    def _commit_prefill(self, flight: "_Flight", nxt: int, last) -> int:
        """What a prefill program leaves behind; returns the tokens it
        produced (one on a prompt's last chunk, none before)."""
        work, ver = flight.work, flight.ver
        seq = work.seq
        sp = seq.sampling
        with self.phases.phase("commit"):
            if seq.state is not SeqState.RUNNING:
                # aborted while the program ran: its pages may already
                # belong to someone else
                self._overlap["discarded_tokens"] += len(flight.sampled)
                return 0
            self._chunks += 1
            seq.note_phase("prefill")  # chunk + its scheduling gap
            with self._lock:
                # full pages covered by this chunk are now shareable
                self.scheduler.register_prefilled_pages(seq, work.end)
            if not work.is_last:
                return 0  # intermediate chunk: no token was produced
            if seq.first_token_at is None:
                self._observe_ttft(seq)
            if sp.logprobs:
                seq.logprobs.append(
                    self._logprob_of(last, nxt, sp.temperature))
            with self._lock:
                seq.token_versions.append(ver)
                done = self.scheduler.commit_token(seq, nxt)
        with self.phases.phase("emit"):
            self._note_gap(seq, time.perf_counter(), flight.ahead)
            self._emit_token(seq, nxt, ver)
            if done:
                self._finalize(seq)
        return 1

    def _observe_ttft(self, seq: Sequence) -> None:
        now = time.monotonic()
        self._m_ttft.observe(
            (now - seq.enqueued_at) * 1e3, tags=self._tags)
        # TTFT split for the SLO plane: queue vs prefill work
        ph = seq.phases
        self._m_slo_ttft.observe(
            (ph.get("queue", 0.0) + ph.get("preempt", 0.0)) * 1e3,
            tags={"model": self.config.model, "phase": "queue"})
        self._m_slo_ttft.observe(
            (ph.get("prefix_match", 0.0) + ph.get("prefill", 0.0)) * 1e3,
            tags={"model": self.config.model, "phase": "prefill"})
        self._m_slo_ttft.observe(
            (now - seq.enqueued_at) * 1e3,
            tags={"model": self.config.model, "phase": "total"})

    def _launch_decode(self, flight: "_Flight") -> None:
        """The plain decode program of one step. Under speculation the
        lanes with a draft are set aside (`flight.drafted`) for one
        verify dispatch each once the plain lanes are committed."""
        flight.plain, unread = flight.sampled, flight.unread
        tables = flight.work.tables  # of `sampled`, lane for lane
        if self._proposer is not None:
            with self.phases.phase("prepare"):
                flight.plain, tables = [], []
                for s, t in zip(flight.sampled, flight.work.tables):
                    d = self._propose_for(s)
                    if d:
                        flight.drafted.append((s, d))
                    else:
                        flight.plain.append(s)
                        tables.append(t)
                unread = [0] * len(flight.plain)  # never launched ahead
        if not flight.plain:
            return
        # the lane feeds generated[-1], which LIVES at absolute position
        # pos-1 (it was sampled but never cached): rope/wpe index, the
        # context mask, and the KV scatter all key off that position. A
        # lane with a token still unread is one position on, and feeds
        # the id its last program left on the device
        with self.phases.phase("prepare"):
            items = [DecodeItem(s.last_token if n == 0 else -1,
                                s.pos + n - 1, t,
                                s.sampling.temperature, s.sampling.top_k,
                                s.sampling.top_p, s.slot)
                     for s, n, t in zip(flight.plain, unread, tables)]
        if self.state_slots is not None:
            self.state_slots.note_decode(
                self.runner.decode_bucket(len(items)), len(items),
                self.runner.state_by_kernel)
        flight.handle = self.runner.launch_decode(items)

    def _propose_for(self, seq: Sequence) -> list[int]:
        """Draft tokens for one lane, clamped so every drafted write
        position fits the pages the lane owns, stays below
        max_model_len, and cannot overshoot the request's max_tokens —
        under cache pressure the clamp hits zero and the lane decodes
        exactly as without spec."""
        room = min(
            len(seq.table) * self.pool.block_size - seq.pos,
            self.runner.max_model_len - seq.pos,
            seq.sampling.max_tokens - len(seq.generated) - 1)
        k = min(self._spec_k, room)
        if k <= 0:
            return []
        return self._proposer.propose(
            list(seq.prompt) + list(seq.generated), k)[:k]

    def _commit_decode(self, flight: "_Flight", next_tokens: list[int],
                       logits) -> int:
        """Commit and emit what a decode program sampled. A lane that
        ended while the program ran (an eos in the step before it, an
        abort) gets nothing: its id is dropped."""
        ver = flight.ver
        with self.phases.phase("commit"):
            lanes = [(i, s, tok) for i, (s, tok) in enumerate(
                zip(flight.plain, next_tokens))
                if s.state is SeqState.RUNNING]
            self._overlap["discarded_tokens"] += len(next_tokens) - len(lanes)
            for i, s, tok in lanes:
                if s.sampling.logprobs:
                    s.logprobs.append(self._logprob_of(
                        logits[i], tok, s.sampling.temperature))
            now = time.monotonic()
            for _, s, _ in lanes:
                s.note_phase("decode", now)  # step + its scheduling gap
            finished = []
            with self._lock:
                for _, s, tok in lanes:
                    s.token_versions.append(ver)
                    if self.scheduler.commit_token(s, tok):
                        finished.append(s)
        with self.phases.phase("emit"):
            now = time.perf_counter()  # one reading for the step's lanes
            for _, s, tok in lanes:
                self._note_gap(s, now, flight.ahead)
                self._emit_token(s, tok, ver)
            for s in finished:
                self._finalize(s)
        return len(lanes)

    def _note_gap(self, seq: Sequence, now: float, ahead: bool) -> None:
        """A token of `seq` is emitted at `now` by a step launched
        `ahead` of the read before it or not: the gap since the
        request's last token goes to one of GAP_CAUSES, first match
        wins. Its first token has none."""
        last = seq.emit_at
        if last is not None:
            if seq.preemptions != seq.emit_preemptions:
                cause = "after_preempt"
            elif self._prefill_reads != seq.emit_prefills:
                cause = "after_prefill"
            elif not ahead:
                cause = "after_drain"
            else:
                cause = "decode"
            ms = (now - last) * 1e3
            self._gaps[cause].add(ms)
            pending = self._itl_pending.get(cause)
            if pending is None:
                pending = self._itl_pending[cause] = [{}, 0.0]
            i = bisect.bisect_left(ITL_BOUNDS_MS, ms)
            pending[0][i] = pending[0].get(i, 0) + 1
            pending[1] += ms
        seq.emit_at = now
        seq.emit_prefills = self._prefill_reads
        seq.emit_preemptions = seq.preemptions

    def _verify_one(self, seq: Sequence, draft: list[int],
                    ver: int) -> int:
        """One speculative step for one lane: a single verify dispatch
        scores the frontier token plus the drafts, the acceptance rule
        runs in-jit, and every returned token is already backed by KV —
        commit them in order (stopping if the lane retires mid-run on
        eos / max_tokens) and emit with explicit stream indices.
        Returns the tokens committed."""
        sp = seq.sampling
        # the dispatch's latency is the runner's three phases of it
        spent = self.phases.seconds
        before = spent["prepare"] + spent["dispatch"] + spent["fetch"]
        try:
            tokens, logits = self.runner.verify(
                seq.last_token, seq.pos - 1, draft, seq.tables,
                sp.temperature, sp.top_k, sp.top_p)
        except Exception as e:  # noqa: BLE001
            with self._lock:
                self.scheduler.abort(seq, f"error:{e!r}")
            self._finalize(seq)
            return 0
        with self.phases.phase("commit"):
            self._m_verify_ms.observe(
                (spent["prepare"] + spent["dispatch"] + spent["fetch"]
                 - before) * 1e3, tags=self._tags)
            n_acc = len(tokens) - 1
            self._spec_proposed_total += len(draft)
            self._spec_accepted_total += n_acc
            seq.note_phase("verify", time.monotonic())
            committed: list[int] = []
            done = False
            with self._lock:
                for i, tok in enumerate(tokens):
                    if sp.logprobs:
                        seq.logprobs.append(self._logprob_of(
                            logits[i], tok, sp.temperature))
                    seq.token_versions.append(ver)
                    committed.append(tok)
                    if self.scheduler.commit_token(seq, tok):
                        done = True
                        break
        with self.phases.phase("emit"):
            base = len(seq.generated) - len(committed)
            # the dispatch's first token carries the gap; the rest of
            # its run comes with it
            self._note_gap(seq, time.perf_counter(), ahead=False)
            self._burst_tokens += len(committed) - 1
            for j, tok in enumerate(committed):
                self._emit_token(seq, tok, ver, index=base + j)
            if done:
                self._finalize(seq)
        return len(committed)

    # ------------------------------------------------------------ output

    def _logprob_of(self, logits, token: int, temperature: float) -> float:
        """See runner.logprob_at — the ONE logprob definition shared
        with the RL learner's teacher-forced reference."""
        from ray_tpu.serve.llm.runner import logprob_at

        return logprob_at(logits, token, temperature,
                          self.model_cfg.vocab_size)

    def _emit_token(self, seq: Sequence, token: int,
                    version: int, index: int | None = None) -> None:
        """`version` is the step-stable weight version the caller read
        under `_step_lock` — required, so a token can never be tagged
        from a concurrent swap's half-installed state. `index` is the
        token's stream position; None means "the latest" (single-token
        commits) — speculative steps commit several tokens before
        emitting and pass each one's index explicitly."""
        with self._lock:
            stream = self._streams.get(seq.seq_id)
        if stream is not None:
            idx = len(seq.generated) - 1 if index is None else index
            ev = {"token": int(token), "index": idx}
            if seq.sampling.logprobs:
                ev["logprob"] = seq.logprobs[idx]
                ev["weight_version"] = version
            stream._emit(ev)
            self._pushed_to = stream._writer or self._pushed_to

    def _finalize(self, seq: Sequence) -> None:
        with self._lock:
            stream = self._streams.pop(seq.seq_id, None)
        if stream is None:
            return  # already finalized (idempotent: no double-count)
        outcome = (seq.finish_reason or "unknown").split(":", 1)[0]
        # ---- latency attribution: close the waterfall -----------------
        now = time.monotonic()
        # the tail interval (last step end -> this close): queue time if
        # the request never ran (aborted while waiting), else emit
        seq.note_phase("emit" if seq.phases else "queue", now)
        e2e = now - seq.enqueued_at
        breakdown = {k: round(v, 6) for k, v in seq.phases.items()}
        breakdown["e2e"] = round(e2e, 6)
        # TPOT divides decode-side wall time over the tokens actually
        # committed: speculative steps commit several tokens per verify
        # dispatch, so both the verify phase and the full token count
        # enter the quotient (one-token-per-dispatch was only ever true
        # spec-off)
        dec_s = seq.phases.get("decode", 0.0) + seq.phases.get(
            "verify", 0.0)
        if len(seq.generated) > 1 and dec_s > 0:
            self._m_slo_tpot.observe(
                dec_s * 1e3 / (len(seq.generated) - 1),
                tags=self._tags)
        with self._lock:
            self._finished_requests += 1
            self._outcomes[outcome] = self._outcomes.get(outcome, 0) + 1
            for k, v in seq.phases.items():
                self._phase_totals[k] = self._phase_totals.get(k, 0.0) + v
        self._record_request_spans(seq, now)
        versions = sorted(set(seq.token_versions))
        final = {
            "done": True,
            "finish_reason": seq.finish_reason,
            "num_generated": len(seq.generated),
            "token_ids": list(seq.generated),
            "preemptions": seq.preemptions,
            # prompt tokens served from the prefix cache at the last
            # admission (vLLM/OpenAI `cached_tokens` usage field)
            "cached_tokens": seq.cached_tokens,
            # weight-version contract (RL.md): `weight_version` is the
            # version the stream finished on; `stale` means the tokens
            # (or the KV they were decoded against) span more than one
            # version, so logprobs are NOT reproducible by a teacher-
            # forced forward at any single version
            "weight_version": (versions[-1] if versions
                               else self._weight_version),
            "weight_versions": versions,
            "stale": seq.kv_stale or len(versions) > 1,
        }
        final["breakdown"] = breakdown
        if seq.sampling.echo:
            final["prompt_token_ids"] = list(seq.prompt)
        if seq.sampling.logprobs:
            final["logprobs"] = list(seq.logprobs)
        stream._close(final)

    # deterministic waterfall order for the laid-out request spans
    _PHASE_ORDER = ("queue", "prefix_match", "prefill", "preempt",
                    "decode", "verify", "emit")

    def _record_request_spans(self, seq: Sequence, now: float) -> None:
        """Emit the request's waterfall as child spans: one parent
        `llm.request` over [enqueue, close] plus one child per nonzero
        phase, laid out contiguously in waterfall order (phases
        interleave in real time — a round of prefill chunks, then a
        decode step — so the contiguous layout is the readable summary, and
        the durations are the exact per-phase totals). All hang off the
        request's propagated trace context."""
        from ray_tpu.utils.events import child_trace

        tracing.record_interval("llm.request", seq.enqueued_at, now,
                                category="serve", trace=seq.trace)
        cursor = seq.enqueued_at
        for phase in self._PHASE_ORDER:
            dur = seq.phases.get(phase, 0.0)
            if dur <= 0.0:
                continue
            tracing.record_interval(
                f"llm.request.{phase}", cursor, cursor + dur,
                category="serve", trace=child_trace(seq.trace))
            cursor += dur

    # ------------------------------------------------------------- admin

    @property
    def weight_version(self) -> int:
        return self._weight_version

    def update_weights(self, version: int, params: Any) -> dict:
        """Drain-free weight hot-swap, installed at a step boundary.

        Under `_step_lock`, with the step in flight read first, no
        device program is pending: the swap slots cleanly BETWEEN engine
        steps, so every token carries the version of the program that
        sampled it — in-flight streams are never dropped, they simply
        continue on the new weights. Semantics (documented in RL.md, test-gated):

        - tokens already sampled keep their old version tags; tokens
          sampled after the swap are tagged `version`;
        - running sequences keep their old-version KV pages and decode
          new tokens against them with the new weights — their final
          event is tagged ``stale`` (mixed versions, logprobs not
          reproducible at any single version);
        - the prefix cache is invalidated (old-weight KV must never be
          matched by a post-swap admission) and stale sequences stop
          registering pages;
        - `version` must be strictly increasing.

        Returns swap stats (previous version, wall time, in-flight
        stream count, registrations dropped)."""
        t0 = time.perf_counter()
        with self._before_the_loop(), self._step_lock:
            if version <= self._weight_version:
                raise ValueError(
                    f"weight version must increase: engine at "
                    f"{self._weight_version}, got {version}")
            # the step in flight ran on the old weights: read it and
            # tag its tokens so before the new tree is installed
            self._drain("swap")
            with tracing.span("rl.weight_swap"):
                self.runner.set_params(params)
                dropped = self.pool.invalidate_prefix_cache()
                with self._lock:
                    previous = self._weight_version
                    self._weight_version = version
                    running = list(self.scheduler.running)
                    for s in running:
                        s.kv_stale = True
                    in_flight = len(running) + len(self.scheduler.waiting)
        dt = time.perf_counter() - t0
        self._m_swap_s.observe(dt, tags=self._tags)
        return {"version": version, "previous_version": previous,
                "swap_seconds": dt, "in_flight_streams": in_flight,
                "registrations_dropped": dropped}

    def warmup(self) -> int:
        """Precompile every bucketed program (prefill lengths x decode
        batch sizes) so no request pays a mid-stream XLA compile;
        returns the compiled-program count."""
        with self._step_lock:
            self._drain(None)
            before = tracing.compile_totals()
            t0 = time.perf_counter()
            programs = self.runner.warmup()
            wall = time.perf_counter() - t0
            spent = tracing.compile_totals(since=before)
            # what warm-up fetched, routed, read and timed is no step's
            self.phases.seconds.update(
                dict.fromkeys(self.phases.seconds, 0.0))
            for by_kind in (self.runner.launch, self.runner.fetch):
                for n in by_kind.values():
                    n.update(dict.fromkeys(n, 0))
            self.runner.fetched_bytes = 0
            self.runner.take_expert_pairs()
            for by in (self.runner.context_slots, self.runner.rows_written,
                       *self.runner.context_by_kind.values()):
                for n in by.values():
                    n.update(dict.fromkeys(n, 0))
        up = self._startup
        up["warmup"] += wall
        up["warmup_trace"] += spent["trace"]
        up["warmup_lower"] += spent["lower"]
        # XLA's compile, or the load from the persistent cache on a hit
        up["warmup_compile"] += spent["backend_compile"]
        self._warmup_cache["hits"] += int(spent["hits"])
        self._warmup_cache["misses"] += int(spent["misses"])
        return programs

    @contextlib.contextmanager
    def _before_the_loop(self):
        """Around taking `_step_lock` in a caller that is not the loop:
        the loop's next turn waits until the caller is done."""
        with self._lock:
            self._urgent += 1
        try:
            yield
        finally:
            with self._lock:
                self._urgent -= 1

    def has_work(self) -> bool:
        with self._lock:
            return bool(self.scheduler.waiting or self.scheduler.running
                        or self._flights)

    def stats(self) -> dict:
        import jax

        devices = jax.devices()
        d = self.scheduler.depth()
        with self._lock:
            phase_totals = dict(self._phase_totals)
            finished = self._finished_requests
            outcomes = dict(self._outcomes)
        d.update({
            # the page pools by kind of KV layer (cache.KVPools.stats),
            # and the rows the programs stored in them, by path
            "kv": {name: {**pool, **{
                "rows_written_" + path: n for path, n in
                self.runner.rows_written[name].items()},
                # bytes a token takes in the kind's layers, by sort of
                # row: k and v, or a latent kind's latent and index rows
                "token_bytes": lay.token_bytes(
                    self.runner.k_pages[i].dtype.itemsize)}
                for i, (lay, (name, pool)) in enumerate(zip(
                    self.runner.layouts, self.kv.stats().items()))},
            "model": self.config.model,
            "block_size": self.pool.block_size,
            "max_batch_size": self.config.max_batch_size,
            "max_model_len": self.runner.max_model_len,
            "compiled_programs": self.runner.compiled_signatures(),
            "weight_version": self._weight_version,
            # cumulative waterfall over finished requests — surfaced
            # per replica by util.state.llm_status()
            "phase_seconds": phase_totals,
            "finished_requests": finished,
            "finished_by_outcome": outcomes,
            "tokens_generated": self._tokens,
            "prefill_chunks": self._chunks,
            # the step loop's own account (see OBSERVABILITY.md): host
            # seconds by phase, steps and bytes fetched by step kind,
            # and where this replica's start went
            "step_phase_seconds": dict(self.phases.seconds),
            # the loop's wall time since its first call of step(), and
            # its turns by the kind of step they read; what `wall_s`
            # holds beyond the phases' sum ran under no phase (or is the
            # phase the loop is in right now)
            "loop": {
                "wall_s": (time.perf_counter() - self._loop_t0
                           if self._loop_t0 is not None else 0.0),
                "turns": {kind: {
                    "count": sum(t.counts), "wall_s": t.sum_ms / 1e3,
                    "max_ms": t.max_ms, "hist": list(t.counts),
                    "over_250ms_s": self._slow_turn_s[kind]}
                    for kind, t in self._turns.items()}},
            # every gap between two tokens of one request, where the
            # loop emits them, by cause (GAP_CAUSES), on `edges_ms`;
            # `burst`: tokens a verify dispatch committed after its first
            "token_gaps": {
                "edges_ms": list(GAP_EDGES_MS),
                "by_cause": {c: list(d.counts)
                             for c, d in self._gaps.items()},
                "sum_ms": {c: d.sum_ms for c, d in self._gaps.items()},
                "max_ms": {c: d.max_ms for c, d in self._gaps.items()},
                "burst": self._burst_tokens},
            # the replica's half of the streams' hand-off to the client
            "stream": self._stream_account.stats(),
            "steps": dict(self._steps),
            "d2h_bytes": dict(self._d2h),
            # the jitted call alone, by kind of program: calls, wall
            # seconds, and the host arrays the calls handed the runtime
            # with their bytes, beside the leaves every call hands over
            # already on the device; and a fetch's wait for the program
            # apart from the copy after it and the rows put back in the
            # caller's order
            "launch": {"resident_leaves": self.runner.resident_leaves,
                       **{kind: dict(n) for kind, n in
                          self.runner.launch.items()}},
            "fetch": {kind: dict(n) for kind, n in
                      self.runner.fetch.items()},
            # slots of cached context by kind of program: read as
            # launched, valid, and what a read to max_model_len would be
            "context": {kind: dict(n) for kind, n in
                        self.runner.context_slots.items()},
            # the same a kind of KV layer: {kv kind: {program: counts}},
            # with `kernel_steps`: the launches whose read of the kind
            # ran the Pallas kernel (ops/paged_attention.py)
            "context_by_kind": {
                name: {kind: dict(n) for kind, n in by.items()}
                for name, by in self.runner.context_by_kind.items()},
            # steps launched while the one before was unread, or not, by
            # kind; why not, by reason; sampled ids no stream got
            "overlap": {k: dict(v) if isinstance(v, dict) else v
                        for k, v in self._overlap.items()},
            "in_flight": len(self._flights),
            "startup_seconds": dict(self._startup),
            "warmup_cache": dict(self._warmup_cache),
            "init_cache": dict(self._init_cache),
            # the resident parameter tree: its bytes, the leaves the last
            # install converted to their resident dtype, installs so far
            "weights": dict(self.runner.weights),
            "spec_proposed": self._spec_proposed_total,
            "spec_accepted": self._spec_accepted_total,
            # recurrent state: slots, bytes, prefill programs that
            # started a slot from zero (admissions that looked up no
            # prefix) and that started from a carried state, decode
            # steps by rows with the slots they owned; {} for a family
            # with none
            "state": (self.state_slots.stats() if self.state_slots
                      else {}),
            # routed experts by step kind: pairs computed, the same per
            # expert (summed over layers), those on the experts held here,
            # experts touched and layers run (both summed over programs
            # and layers); {} for a dense model
            "moe": {kind: {**acc, "expert_pairs":
                           acc["expert_pairs"].tolist()}
                    for kind, acc in list(self._moe.items())},
            # the device this replica's process runs on, as jax reports it
            "platform": devices[0].platform,
            "device_kind": devices[0].device_kind,
            "device_count": len(devices),
        })
        return d

    def abort_request(self, stream: RequestStream,
                      reason: str = "aborted") -> None:
        """Close a request out between two steps. A token of its lane
        that a step in flight samples is read and dropped."""
        with self._before_the_loop(), self._step_lock:
            with self._lock:
                seqs = [s for s in
                        list(self.scheduler.waiting) + self.scheduler.running
                        if s.seq_id == stream.seq_id]
                for s in seqs:
                    self.scheduler.abort(s, reason)
            if any(s.inflight for s in seqs):
                self._drain("abort")
            for s in seqs:
                self._finalize(s)
