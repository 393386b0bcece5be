"""Continuous-batching scheduler (reference shape: vLLM's scheduler,
reduced to the TPU-static-shape essentials) with automatic prefix
caching and chunked prefill.

State machine per sequence::

    WAITING --admit--> RUNNING(prefilling -> decoding) --eos/cap--> FINISHED
       ^                  |
       +---- preempt -----+   (cache pool exhausted)

Policy, chosen per step by `schedule()`:

- **prefill-first admission**: if a waiting sequence fits (a free
  decode lane AND enough free pages), admit it. Admission first runs a
  longest-prefix match against the content-addressed pool — full pages
  whose hash chain is already cached are *shared* (refcount +1) and
  skipped entirely; only the remaining pages are allocated and only the
  remaining tokens are prefilled;
- an admitted sequence prefills its (unmatched) prompt in page-aligned
  **chunks** of at most `chunk_size` tokens. Continuation chunks and
  decode steps go in **rounds**: a decode step counts the lanes still
  prefilling, and that many chunks (the oldest such lane's first, an
  admission's first chunk among them) run before the next decode step,
  so every lane that holds a slot moves once a round: a chunk if it is
  prefilling, a token if it is decoding. One lane prefilling is one
  chunk, then one decode step. A decoding lane thus waits for at most
  one chunk of each lane that holds a slot (fewer than `max_batch_size`)
  between two of its tokens, whatever the prompts' lengths, and one long
  prompt stalls the batch by one chunk a round, not by its whole
  prefill. Admissions' first chunks run back to back with no bound of
  their own: a free lane is filled at once;
- otherwise **decode** every fully-prefilled sequence in one batched
  step; before it, any lane crossing a page boundary gets one new page;
  if the pool is dry, the **most recently admitted** lane is preempted
  (recompute-style: its page refs are dropped and it re-enters the
  waiting queue FRONT with prompt+generated as its new prompt — with
  greedy sampling its continuation is bit-identical, which the tests
  assert). LIFO victim choice protects the oldest sequences' progress.
  A preempted sequence's pages usually survive in the pool's LRU, so
  its re-admission prefix-matches them back instead of re-prefilling.

Page registration: a page becomes shareable the moment its KV content
is completely written — after the prefill chunk covering it, or after
the decode step that fills its last slot. The hash chain covers
prompt AND generated tokens, so shared prefixes survive preemption and
even extend into generated text (RL-style rollouts forking one prompt).

Planning ahead: the engine plans a step while the one before it is still
on the device, its sampled tokens unread (`Sequence.inflight` counts
them). `schedule()` then takes every lane in flight to continue: one
token more, a page more where that crosses a page. A lane that the step
in flight is known to end (`max_tokens`, `max_model_len`) leaves
`running` at once, so it is in no later step and its lane can be given
away; only its pages wait for the commit. What the plan cannot make
without the results, a preemption, it refuses (`NeedsResults`).

KV by kind of layer (cache.py `KVPools`): a sequence has a block table
a kind. A full kind's covers the whole sequence, allocated at admission
and grown a page at a time by decode. A **window kind's** covers only
what a later row can still see: admission allocates the first chunk's
pages, every later chunk and decode step first gives back the pages
wholly behind the window of its first row (`_cover`), then allocates what
its own rows need, so the table holds at most `KVLayout.lane_pages`
pages. The pages are given back when the step that no longer needs them
is PLANNED: every program that read them was planned, and so enqueued,
before it, and every program that may write them as another lane's is
planned, and so enqueued, after it. The device runs programs in the
order they were enqueued, which is what makes the reuse safe with a step
in flight (and what already made it safe to free a finished lane's pages
while the step behind its last still runs). A family with a window kind
takes no prefix match (`KVPools`): its admissions are counted as matches
declined. Exhaustion of either kind's pool preempts alike.

The scheduler owns no locks: the engine serializes calls. The pool's
internal `_lock` is a leaf — taken inside pool calls only, never
around scheduler state — so there is no lock-order cycle with the
engine's `_lock`.
"""

from __future__ import annotations

import dataclasses
import enum
import time
from collections import deque

from ray_tpu.serve.llm.cache import (
    BlockPool,
    CacheExhausted,
    KVPools,
    hash_page,
    window_lane_pages,
)
from ray_tpu.serve.llm.config import SamplingParams


class NeedsResults(Exception):
    """`schedule(may_preempt=False)` would have to preempt: the caller
    reads the step in flight first and schedules again."""


class SeqState(enum.Enum):
    WAITING = "waiting"
    RUNNING = "running"
    FINISHED = "finished"


@dataclasses.dataclass
class Sequence:
    """One request's scheduling view."""

    seq_id: int
    prompt: list[int]
    sampling: SamplingParams
    state: SeqState = SeqState.WAITING
    generated: list[int] = dataclasses.field(default_factory=list)
    # physical page ids in logical order, one table a kind of KV layer;
    # a window kind's leading `released[kind]` entries are the null page
    # (their pages went back to the pool behind the window)
    tables: list[list[int]] = dataclasses.field(
        default_factory=lambda: [[]])
    released: list[int] = dataclasses.field(default_factory=lambda: [0])
    last_token: int = -1  # input to the next decode step
    # tokens sampled by steps that are launched and not yet committed
    # (0..2: the step whose results are being read, and the one behind)
    inflight: int = 0
    # index into the runner's device-resident "last sampled id" array,
    # held while the sequence is in `running`; -1: none
    slot: int = -1
    preemptions: int = 0
    # chunked-prefill progress: [0, prefilled) of refill_tokens is
    # scattered into `table`; the goal is `prefill_target` (the refill
    # length at admission — refill_tokens keeps growing as decode
    # appends, but those positions are written by decode steps). The
    # scheduler marks a chunk prefilled when it ISSUES the work; the
    # engine executes it before the next schedule() call.
    prefilled: int = 0
    prefill_target: int = 0
    # prefix-cache accounting: tokens skipped at the last admission,
    # and how many leading pages of `table` are content-registered
    cached_tokens: int = 0
    registered_pages: int = 0
    # weight hot-swap bookkeeping (RL flywheel): the engine's weight
    # version at the step that sampled each generated token, and —
    # when SamplingParams.logprobs — the sampled token's log-prob under
    # the distribution it was drawn from. Both survive preemption
    # (recompute replays the tokens, it does not resample them).
    token_versions: list[int] = dataclasses.field(default_factory=list)
    logprobs: list[float] = dataclasses.field(default_factory=list)
    # set by the engine on running sequences at a weight swap: this
    # sequence's KV pages mix weight versions, so they must never be
    # content-registered (a later match would reuse stale KV) and the
    # trajectory is tagged stale. Cleared on preemption — recompute
    # rebuilds the whole table under one consistent version.
    kv_stale: bool = False
    enqueued_at: float = dataclasses.field(default_factory=time.monotonic)
    first_token_at: float | None = None
    finish_reason: str | None = None
    # the engine's token-gap account (`LLMEngine._note_gap`): the
    # loop's `perf_counter` at this request's last emitted token, and
    # what the engine's count of prefill steps read and `preemptions`
    # stood at then
    emit_at: float | None = None
    emit_prefills: int = 0
    emit_preemptions: int = 0
    # ---- latency attribution (the per-request waterfall) ----
    # Interval accounting: `_mark` is where attribution left off; every
    # phase transition charges [_mark, now) to ONE phase and advances
    # the mark, so the phases always sum to exactly the wall time from
    # enqueue to the last transition — the property the breakdown's
    # "sums to e2e" contract rests on. Phases: queue (waiting for
    # admission), prefix_match (the successful admission's cache
    # lookup), prefill (chunk execution, incl. recompute after
    # preemption), decode (decode steps + their scheduling gaps),
    # preempt (evicted, waiting for re-admission), emit (finalize tail).
    phases: dict[str, float] = dataclasses.field(default_factory=dict)
    _mark: float = dataclasses.field(default_factory=time.monotonic)
    _preempt_wait: bool = False  # between preemption and re-admission
    # request trace context (set by the engine at add_request): the
    # finalize-time waterfall spans hang off this, so one request's
    # phase spans correlate with its handle/proxy spans by trace_id
    trace: dict | None = None

    def note_phase(self, phase: str, now: float | None = None) -> None:
        """Charge the interval since the last mark to `phase`."""
        if now is None:
            now = time.monotonic()
        self.phases[phase] = self.phases.get(phase, 0.0) \
            + max(0.0, now - self._mark)
        self._mark = now
    # lazily extended hash chain over prompt+generated full pages
    _hashes: list[int] = dataclasses.field(default_factory=list)

    @property
    def table(self) -> list[int]:
        """The first kind's table (the only one, for most families)."""
        return self.tables[0]

    @property
    def refill_tokens(self) -> list[int]:
        """What prefill must run over: the original prompt plus anything
        generated before a preemption (recompute-style resume)."""
        return self.prompt + self.generated

    @property
    def pos(self) -> int:
        """prompt+generated length. The cache holds positions
        0..pos-2 (the last generated token is sampled but not yet
        cached); the next decode step feeds it at position pos-1 and
        writes its KV there."""
        return len(self.prompt) + len(self.generated)

    def ends_in_flight(self, max_model_len: int) -> bool:
        """Will the tokens in flight end this sequence, whatever they
        are? (An eos among them cannot be known: it costs one discarded
        lane-step.)"""
        n = self.inflight
        return n > 0 and (
            len(self.generated) + n >= self.sampling.max_tokens
            or self.pos + n >= max_model_len)

    @property
    def prefill_pending(self) -> bool:
        return self.state is SeqState.RUNNING \
            and self.prefilled < self.prefill_target

    def page_hashes(self, n_pages: int, block_size: int) -> list[int]:
        """Hash chain over the first `n_pages` full pages of
        prompt+generated (extends the cached chain; earlier entries are
        append-only stable because tokens only ever append)."""
        if n_pages > len(self._hashes):
            all_tokens = self.prompt + self.generated
            prev = self._hashes[-1] if self._hashes else 0
            for k in range(len(self._hashes), n_pages):
                prev = hash_page(
                    prev, all_tokens[k * block_size:(k + 1) * block_size])
                self._hashes.append(prev)
        return self._hashes[:n_pages]

    def eos_hit(self, token: int) -> bool:
        return token in self.sampling.eos_set()


@dataclasses.dataclass
class PrefillWork:
    """Prefill refill_tokens[start:end] at position offset `start`
    (page-aligned). `is_last` marks the chunk that reaches the end of
    the prompt — the engine samples the first generated token from it."""

    seq: Sequence
    start: int = 0
    end: int = 0
    is_last: bool = True
    # the sequence's tables as this program reads and writes them, one a
    # kind (`Scheduler._tables_now`)
    tables: list[list[int]] = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class DecodeWork:
    seqs: list[Sequence]
    # each lane's tables as this step reads and writes them
    tables: list[list[list[int]]] = dataclasses.field(default_factory=list)


class Scheduler:
    def __init__(self, pool: "BlockPool | KVPools", *, max_batch_size: int,
                 max_model_len: int, chunk_size: int = 0,
                 spec_tokens: int = 0):
        # a pool a kind of KV layer; `pool`: the first kind's, which the
        # prefix index addresses
        self.kv = pool if isinstance(pool, KVPools) else KVPools.single(pool)
        self.pool = self.kv.pools[0]
        # (index, window) of the window kinds: most families have none
        self._windows = [(i, kind.window)
                         for i, kind in enumerate(self.kv.kinds)
                         if kind.window is not None]
        self.max_batch_size = max_batch_size
        self.max_model_len = max_model_len
        # page-aligned by construction (the engine rounds it); 0 means
        # "whole prompt in one chunk" (monolithic prefill)
        self.chunk_size = chunk_size
        # speculative decoding: opportunistically grow tables so a
        # drafted run of up to spec_tokens extra KV slots fits (0 = off)
        self.spec_tokens = spec_tokens
        self.waiting: deque[Sequence] = deque()
        self.running: list[Sequence] = []  # admission order (LIFO victim)
        self._free_slots = list(range(max_batch_size - 1, -1, -1))
        self.preemption_count = 0
        self.prefix_hit_pages = 0
        self.prefix_miss_pages = 0
        # continuation chunks this round may still issue before its decode
        # step: the lanes that were prefilling when the last one was planned,
        # less the chunks issued since (under 0 where more were)
        self._chunks_due = 0
        self._round_chunks = 0  # continuation chunks since that step
        # what the rounds came to (`depth()`): decode steps planned and the
        # ready lanes summed over them, continuation chunks, and the decode
        # steps that more than one continuation chunk preceded
        self.decode_steps = 0
        self.decode_lanes = 0
        self.continuation_chunks = 0
        self.multi_chunk_rounds = 0
        # sequences retired INSIDE schedule() (length cap backstop,
        # cache_exhausted fail-loud) — the engine drains these every
        # step so their streams still get closed
        self.retired_in_schedule: list[Sequence] = []

    # ------------------------------------------------------------ intake

    def add(self, seq: Sequence) -> None:
        if len(seq.prompt) >= self.max_model_len:
            raise ValueError(
                f"prompt of {len(seq.prompt)} tokens needs at least one "
                f"free position below max_model_len={self.max_model_len}")
        self.waiting.append(seq)

    def abort(self, seq: Sequence, reason: str = "aborted") -> None:
        if seq.state is SeqState.RUNNING:
            if seq in self.running:  # not one its last step set aside
                self.running.remove(seq)
        elif seq.state is SeqState.WAITING:
            try:
                self.waiting.remove(seq)
            except ValueError:
                pass
        self._finish(seq, reason)

    # ---------------------------------------------------------- planning

    def schedule(self, may_preempt: bool = True
                 ) -> PrefillWork | DecodeWork | None:
        """Pick the next unit of work. Admission never preempts: a
        waiting sequence only enters when pages are genuinely free.
        With a step in flight the caller passes ``may_preempt=False``:
        a decode step that would need a preemption raises
        `NeedsResults` instead."""
        for seq in [s for s in self.running
                    if s.ends_in_flight(self.max_model_len)]:
            # its last step is on the device: in no later one
            self.running.remove(seq)
            self._release_slot(seq)
        work = self._try_admit()
        if work is not None:
            self._chunks_due -= 1  # the newcomer's chunk of this round
            return work
        pending = [s for s in self.running if s.prefill_pending]
        ready = [s for s in self.running if not s.prefill_pending]
        # a continuation chunk, the oldest prefilling lane's: as many a
        # round as lanes were prefilling at its start, so that the decode
        # step behind them takes every lane they made ready and a long
        # prompt can't monopolize steps; they go on alone while nothing
        # is decodable yet
        if pending and (self._chunks_due > 0 or not ready):
            work = self._next_chunk(pending[0], may_preempt)
            if work is None:  # the chunk's own lane was preempted for room
                return self.schedule(may_preempt)
            self._chunks_due -= 1
            self._round_chunks += 1
            self.continuation_chunks += 1
            return work
        if not ready:
            return None
        self._grow_tables_or_preempt(may_preempt)
        ready = [s for s in self.running if not s.prefill_pending]
        if not ready:
            return None
        # the round ends here and the next begins: a chunk for each lane
        # still prefilling, then the next decode step
        self.decode_steps += 1
        self.decode_lanes += len(ready)
        self.multi_chunk_rounds += self._round_chunks > 1
        self._chunks_due = len(self.running) - len(ready)
        self._round_chunks = 0
        if not self._windows:  # tables that only grow: handed over as they are
            return DecodeWork(ready, [s.tables for s in ready])
        return DecodeWork(ready, [self._tables_now(s) for s in ready])

    def _try_admit(self) -> PrefillWork | None:
        if not (self.waiting and len(self.running) < self.max_batch_size):
            return None
        seq = self.waiting[0]
        total = len(seq.refill_tokens)
        n_pages = self.pool.blocks_for_tokens(total)
        bs = self.pool.block_size
        # longest-prefix match over FULL pages, capped so at least one
        # token is left to prefill (its logits sample the first token)
        t_match = time.monotonic()
        matched = self.pool.match_prefix(
            seq.page_hashes((total - 1) // bs, bs))
        # a window kind holds the first chunk's rows to begin with, and
        # is admitted only where its pool has the most it will ever hold
        # (window + a chunk) free: a lane let into a pool nearly dry would
        # be the next victim, and admitted again on the pages it gave up
        rows = min(total, self.chunk_size or total)
        first = self.pool.blocks_for_tokens(rows)
        need = [n_pages - len(matched)] + [
            n_pages if kind.window is None else first
            for kind in self.kv.kinds[1:]]
        room = [n if kind.window is None
                else max(n, window_lane_pages(kind.window, rows, bs))
                for kind, n in zip(self.kv.kinds, need)]
        if not all(pool.can_alloc(n)
                   for pool, n in zip(self.kv.pools, room)):
            if matched:
                self.pool.free(matched)  # drop the refs; stay queued
            return None
        # waterfall: everything up to the successful match attempt was
        # queue time (or preempt-wait time after an eviction); the
        # lookup itself is the prefix_match phase
        seq.note_phase("preempt" if seq._preempt_wait else "queue",
                       t_match)
        seq._preempt_wait = False
        seq.note_phase("prefix_match")
        self.waiting.popleft()
        self.prefix_hit_pages += len(matched)
        self.prefix_miss_pages += n_pages - len(matched)
        self.kv.prefix_taken += bool(matched)
        self.kv.prefix_declines += self.kv.prefix_declined
        seq.tables = [pool.alloc(n)
                      for pool, n in zip(self.kv.pools, need)]
        seq.tables[0] = matched + seq.tables[0]
        seq.released = [0] * len(self.kv.pools)
        self._note_tables(seq)
        seq.prefilled = len(matched) * bs
        seq.prefill_target = total
        seq.cached_tokens = seq.prefilled
        seq.registered_pages = len(matched)
        seq.state = SeqState.RUNNING
        seq.slot = self._free_slots.pop()  # one a lane: never empty
        self.running.append(seq)
        return self._next_chunk(seq)

    def _next_chunk(self, seq: Sequence, may_preempt: bool = True
                    ) -> PrefillWork | None:
        """The next chunk of `seq`'s prompt. A window kind's table first
        moves on with it, which may find its pool dry: preempt (LIFO)
        until it fits, as a decode step does; None where the victim was
        `seq` itself."""
        total = seq.prefill_target
        start = seq.prefilled
        end = min(total, start + (self.chunk_size or total))
        while True:
            try:
                self._cover(seq, end, first_row=start)
                break
            except CacheExhausted:
                if not self._preempt_for(seq, may_preempt):
                    return None
        seq.prefilled = end  # issued == done: the engine runs it now
        return PrefillWork(seq=seq, start=start, end=end,
                           is_last=(end == total),
                           tables=self._tables_now(seq))

    def _tables_now(self, seq: Sequence) -> list[list[int]]:
        """`seq`'s tables for the program being planned. The engine
        launches it only after it has planned the step behind it, which
        gives back more of a window kind's pages: that kind's table is
        copied as it is now. A full kind's only grows, by pages no
        earlier program reads, and is handed over as it is."""
        if not self._windows:
            return seq.tables
        return [table if kind.window is None else list(table)
                for kind, table in zip(self.kv.kinds, seq.tables)]

    def _cover(self, seq: Sequence, upto: int, first_row: int) -> None:
        """Make every table of `seq` hold the pages of positions
        ``[.., upto)`` for a program whose first row is at `first_row`:
        a window kind gives back the pages no row from there on can see
        (all slots at or below ``first_row - window``), then every kind
        grows to `upto`. Raises CacheExhausted with what it got kept."""
        bs = self.pool.block_size
        for i, window in self._windows:
            table, gone = seq.tables[i], seq.released[i]
            behind = min(max(0, first_row - window + 1) // bs, len(table))
            if behind > gone:
                self.kv.pools[i].free(table[gone:behind])
                table[gone:behind] = [0] * (behind - gone)
                self.kv.released[i] += behind - gone
                seq.released[i] = behind
        needed = self.pool.blocks_for_tokens(upto)
        grown = False
        for table, pool in zip(seq.tables, self.kv.pools):
            if len(table) < needed:
                table.extend(pool.alloc(needed - len(table)))
                grown = True
        if grown:
            self._note_tables(seq)

    def _note_tables(self, seq: Sequence) -> None:
        for i, table in enumerate(seq.tables):
            self.kv.largest_table[i] = max(self.kv.largest_table[i],
                                           len(table) - seq.released[i])

    def _preempt_for(self, seq: Sequence, may_preempt: bool) -> bool:
        """A pool is dry under `seq`: preempt the most recently admitted
        lane. False where that was `seq` itself (preempted, or retired as
        the sole runner that cannot fit)."""
        if not may_preempt:
            raise NeedsResults from None
        victim = self.running[-1]
        if victim is seq and len(self.running) == 1:
            # sole runner and the pool can't grow it: engine
            # guarantees pool >= one max-len sequence, so this
            # is unreachable unless misconfigured — fail loud
            self._retire(seq, "error:cache_exhausted")
            self.retired_in_schedule.append(seq)
            return False
        self.preempt(victim)
        return victim is not seq

    def _grow_tables_or_preempt(self, may_preempt: bool = True) -> None:
        """Every decoding lane must own the page its next token writes
        into, in every kind of KV layer, and a window kind's table moves
        on behind it (`_cover`); preempt (LIFO) until the survivors all
        fit. Lanes still mid-prefill pass through untouched: their
        tables move with their chunks. A lane with a token in flight is
        taken one position on."""
        i = 0
        while i < len(self.running):
            seq = self.running[i]
            pos = seq.pos + seq.inflight
            if pos > self.max_model_len:
                # next decode would write at position pos-1 >= cap:
                # close out at the length limit
                self._retire(seq, "length")
                self.retired_in_schedule.append(seq)
                continue
            if seq.prefill_pending:
                i += 1  # its tables move with its chunks
                continue
            # the decode step feeds the token at position pos-1 and
            # writes KV there, so the tables must cover pos tokens
            if not self._windows and len(seq.tables[0]) \
                    >= self.pool.blocks_for_tokens(pos):
                i += 1  # the common turn: nothing to give back or to grow
                continue
            try:
                self._cover(seq, pos, first_row=pos - 1)
                i += 1
            except CacheExhausted:
                if not self._preempt_for(seq, may_preempt) \
                        and not self.running:
                    return
                # re-examine slot i (the same lane, or a new occupant)
        # speculative headroom is best-effort: a drafted run commits up
        # to spec_tokens + 1 positions in one step, so try to cover
        # pos + spec_tokens — but NEVER preempt for it; under pressure
        # the engine just clamps the draft length to the pages owned
        # and decode proceeds exactly as without spec
        if self.spec_tokens:
            for seq in self.running:
                if seq.prefill_pending:
                    continue
                want = self.pool.blocks_for_tokens(
                    min(seq.pos + self.spec_tokens, self.max_model_len))
                if len(seq.table) < want:
                    try:
                        seq.table.extend(
                            self.pool.alloc(want - len(seq.table)))
                    except CacheExhausted:
                        break

    def preempt(self, seq: Sequence) -> None:
        """Recompute-style: drop page refs, requeue at the FRONT so the
        victim re-admits as soon as space frees up. Registered pages the
        victim doesn't share park in the pool's LRU — re-admission
        usually prefix-matches them straight back."""
        # waterfall: close the running interval (decode-stage time, or
        # prefill if the victim was still mid-prefill); everything
        # until re-admission charges to "preempt"
        seq.note_phase("prefill" if seq.prefill_pending else "decode")
        seq._preempt_wait = True
        self.running.remove(seq)
        self._release_slot(seq)
        self._free_tables(seq)
        seq.prefilled = 0
        seq.prefill_target = 0
        seq.cached_tokens = 0
        seq.registered_pages = 0
        seq.kv_stale = False  # re-prefill rebuilds KV on one version
        seq.state = SeqState.WAITING
        seq.preemptions += 1
        self.preemption_count += 1
        self.waiting.appendleft(seq)

    # ----------------------------------------------------------- results

    def commit_token(self, seq: Sequence, token: int) -> bool:
        """Record one generated token; returns True if the sequence is
        now finished."""
        seq.generated.append(token)
        seq.last_token = token
        if seq.first_token_at is None:
            seq.first_token_at = time.monotonic()
        # the decode step that produced `token` wrote KV at the previous
        # position — any page it completed is now shareable
        self.register_prefilled_pages(seq, seq.pos - 1)
        if seq.eos_hit(token):
            self._retire(seq, "eos")
            return True
        if len(seq.generated) >= seq.sampling.max_tokens:
            self._retire(seq, "length")
            return True
        if seq.pos >= self.max_model_len:
            self._retire(seq, "length")
            return True
        return False

    def register_prefilled_pages(self, seq: Sequence,
                                 upto_tokens: int) -> None:
        """Content-register every full page of `seq` whose KV is
        completely written (positions 0..upto_tokens-1). Idempotent via
        seq.registered_pages."""
        if not self.pool.enable_prefix_cache \
                or seq.state is SeqState.FINISHED or seq.kv_stale:
            return
        bs = self.pool.block_size
        full = min(upto_tokens // bs, len(seq.table))
        if full <= seq.registered_pages:
            return
        hashes = seq.page_hashes(full, bs)
        for k in range(seq.registered_pages, full):
            self.pool.register(seq.table[k], hashes[k])
        seq.registered_pages = full

    def _retire(self, seq: Sequence, reason: str) -> None:
        if seq in self.running:
            self.running.remove(seq)
        self._finish(seq, reason)

    def _release_slot(self, seq: Sequence) -> None:
        if seq.slot >= 0:
            self._free_slots.append(seq.slot)
            seq.slot = -1

    def _free_tables(self, seq: Sequence) -> None:
        """Every page `seq` still holds, back to its kind's pool."""
        for pool, table, released in zip(self.kv.pools, seq.tables,
                                         seq.released):
            pool.free(table[released:])
        seq.tables = [[] for _ in self.kv.pools]
        seq.released = [0] * len(self.kv.pools)

    def _finish(self, seq: Sequence, reason: str) -> None:
        self._release_slot(seq)
        self._free_tables(seq)
        seq.state = SeqState.FINISHED
        seq.finish_reason = reason

    def take_retired(self) -> list[Sequence]:
        """Drain sequences retired inside schedule(); caller (the
        engine) closes their streams."""
        out, self.retired_in_schedule = self.retired_in_schedule, []
        return out

    # ------------------------------------------------------------- stats

    def depth(self) -> dict:
        ps = self.pool.stats()
        return {
            "waiting": len(self.waiting),
            "running": len(self.running),
            "blocks_used": self.pool.num_used(),
            "blocks_total": self.pool.usable_blocks,
            "blocks_cached": ps["cached"],
            "cache_utilization": self.pool.utilization(),
            "preemptions": self.preemption_count,
            "prefix_hit_pages": self.prefix_hit_pages,
            "prefix_miss_pages": self.prefix_miss_pages,
            "prefix_evictions": ps["evictions"],
            # the rounds (see __init__)
            "decode_steps": self.decode_steps,
            "decode_lanes": self.decode_lanes,
            "continuation_chunks": self.continuation_chunks,
            "multi_chunk_rounds": self.multi_chunk_rounds,
        }
