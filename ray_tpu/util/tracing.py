"""User-facing tracing: nested in-task spans + trace-context access.

Reference parity: ray.util.tracing (tracing_helper.py:34 span
propagation), minus the OpenTelemetry dependency — spans land in the
process's TaskEventLog and flow to the head's cluster-wide span buffer,
so `ray_tpu.timeline()` shows them on the merged timeline next to the
runtime's own task/actor spans.

    from ray_tpu.util import tracing

    with tracing.span("preprocess"):          # inside a task, a driver,
        with tracing.span("tokenize"):        # or plain local code
            ...

Entering a span makes it the CURRENT trace context: tasks/actor calls
submitted inside it carry a child context, so a whole driver→actor→task
chain shares one trace_id (correlate with the `args` on timeline spans).
Works without an initialized runtime too (bench scripts, bare engines):
spans then collect in a process-local fallback log that `dump()`
exports."""

from __future__ import annotations

import contextlib
import sys
import threading
import time

from ray_tpu.utils.events import TaskEventLog, child_trace

# spans recorded before/without ray_tpu.init() (a bare LLMEngine)
_fallback_log = TaskEventLog()
_fallback_ctx = threading.local()


def _runtime():
    from ray_tpu.core import api

    return api._runtime


def _ctx_and_log():
    rt = _runtime()
    if rt is not None and hasattr(rt, "_ctx") and hasattr(rt, "_events"):
        return rt._ctx, rt._events
    return _fallback_ctx, _fallback_log


def current_trace() -> dict | None:
    """The active {trace_id, span_id, parent_id} context, if any."""
    ctx, _ = _ctx_and_log()
    return getattr(ctx, "trace", None)


def annotate(name: str):
    """A host event named `name` in the device profiler's own trace (a
    `jax.profiler.TraceAnnotation`: plane `/host:CPU`, the calling
    thread's line, the clock the device operations are on), so a gap
    between two device programs can be laid against what the host was
    doing in it. Recorded only while a profile is being taken; entering
    and leaving one costs about half a microsecond otherwise. Nothing
    goes to the span log: this is for loops too hot for it. A process
    that has not imported jax gets a null context and still does not
    import it."""
    # (getattr: another thread may be half way through importing jax)
    profiler = getattr(sys.modules.get("jax"), "profiler", None)
    annotation = getattr(profiler, "TraceAnnotation", None)
    if annotation is None:
        return contextlib.nullcontext()
    return annotation(name)


class PhaseClock:
    """Seconds a loop spent in each of its phases, always on: one
    `perf_counter` pair per phase into a plain dict (`seconds`), and the
    same interval as an `annotate(prefix + name)` event for a device
    profile. Phases do not nest: they are the leaves. Whether they add
    up to the loop's wall time is the loop's to see to, not this
    class's: what runs between two phases is in none (the serve
    engine's loop takes its own wall time and holds the remainder
    under a hundredth of it, `LLMEngine.stats()["loop"]`). One thread
    drives the loop; readers copy `seconds`."""

    def __init__(self, prefix: str, names: tuple[str, ...]):
        self.prefix = prefix
        self.seconds: dict[str, float] = dict.fromkeys(names, 0.0)

    def phase(self, name: str) -> "_Phase":
        return _Phase(self, name)


class _Phase:
    __slots__ = ("clock", "name", "ann", "t0")

    def __init__(self, clock: PhaseClock, name: str):
        self.clock = clock
        self.name = name

    # the event lies inside the interval that is timed, so that what the
    # event costs is in the phase's seconds and not between two phases
    def __enter__(self):
        self.t0 = time.perf_counter()
        self.ann = annotate(self.clock.prefix + self.name)
        self.ann.__enter__()

    def __exit__(self, *exc):
        self.ann.__exit__(*exc)
        self.clock.seconds[self.name] += time.perf_counter() - self.t0


@contextlib.contextmanager
def span(name: str, category: str = "user"):
    """Record a span around the enclosed block and make it the current
    trace context (children — nested spans, submitted tasks, actor
    calls — link to it). Yields the span's trace context. The block also
    shows under the same name in a device profile taken while it runs
    (`annotate`)."""
    ctx, log = _ctx_and_log()
    parent = getattr(ctx, "trace", None)
    trace = child_trace(parent)
    ctx.trace = trace
    try:
        with log.span(name, category, trace=trace), annotate(name):
            yield trace
    finally:
        ctx.trace = parent


def record_span(name: str, duration_s: float, category: str = "user",
                trace: dict | None = None) -> None:
    """Log an already-measured span ending now (for code that timed
    itself — compile hooks, collective wrappers)."""
    _, log = _ctx_and_log()
    t1 = time.monotonic_ns()
    log.record(name, category, t1 - int(duration_s * 1e9), t1,
               trace=trace or current_trace())


def record_interval(name: str, t0_monotonic_s: float,
                    t1_monotonic_s: float, category: str = "user",
                    trace: dict | None = None) -> None:
    """Log a span over an explicit [t0, t1] monotonic-seconds window
    (time.monotonic() readings) — how waterfall producers lay phase
    spans at their true positions instead of 'ending now'."""
    _, log = _ctx_and_log()
    log.record(name, category, int(t0_monotonic_s * 1e9),
               int(t1_monotonic_s * 1e9), trace=trace or current_trace())


def configure_sampling(policy: dict | None) -> None:
    """Install a span sampling policy on this process's active span log
    (``{"max_per_s": N, "categories": {cat: N}}``, 0 = unlimited)."""
    _, log = _ctx_and_log()
    log.configure_sampling(policy)


@contextlib.contextmanager
def profiler_capture(out_dir: str | None):
    """Arm a `jax.profiler.trace` capture window around the enclosed
    block — the device-side (TPU) profile that attributes in-program
    time (collective vs. GEMM vs. copy) the host-side span plane cannot
    see. Guarded no-op on CPU and when `out_dir` is falsy, so bench
    drivers call it unconditionally: on TPU a `--trace` run captures N
    timed steps, on CPU nothing is armed and nothing is written. On any
    other backend a capture that cannot start or stop raises: a profile
    that silently was not taken is worse than none.

    The capture holds device operations and host events (jax's own and
    every `annotate` / `span`), without the Python call tracer, which
    slows the host several times over and so inflates the very idle
    time the profile is read for.

    The capture window rides the span API: a `profiler.capture` span
    (category `profiler`) covers the armed block, and its trace args
    carry the capture path — so the chrome timeline records WHERE the
    device profile for that window lives. Yields the capture directory
    (None when not armed)."""
    if not out_dir:
        # genuinely free no-op: no jax import, no backend init
        yield None
        return
    import jax

    if jax.devices()[0].platform in ("cpu",):
        yield None
        return
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    with jax.profiler.trace(out_dir, profiler_options=options), \
            span("profiler.capture", category="profiler") as trace:
        trace["capture_path"] = out_dir
        yield out_dir


def jit_cache_size(jit_fn) -> int:
    """Compiled-program count of a `jax.jit` callable, or -1 when the
    (private) `_cache_size` API is unavailable. The ONE wrapper around
    that private API — every compile-miss probe (train/spmd.py,
    serve/llm/runner.py) goes through here, so a JAX upgrade breaks
    exactly one call site."""
    try:
        return jit_fn._cache_size()
    except Exception:  # noqa: BLE001
        return -1


def note_compile_if_grew(jit_fn, before: int, duration_s: float,
                         miss_counter, compile_hist, span_name: str,
                         tags: dict | None = None) -> bool:
    """The compile-miss protocol, in one place: if `jit_fn`'s cache grew
    past the `before` reading, account `duration_s` as a compile (miss
    counter + compile histogram + a compile-category span) and return
    True; otherwise return False (the caller accounts a normal step)."""
    if before < 0 or jit_cache_size(jit_fn) <= before:
        return False
    miss_counter.inc(tags=tags)
    compile_hist.observe(duration_s, tags=tags)
    record_span(span_name, duration_s, category="compile")
    return True


# what jax itself reports of a compile, by the stage it names
_COMPILE_STAGES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    # XLA's compile, or on a persistent-cache hit the load that
    # replaces it: jax times both under this one event
    "/jax/core/compile/backend_compile_duration": "backend_compile",
}
_CACHE_RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"
_CACHE_RESULTS = {
    "/jax/compilation_cache/cache_hits": "hits",
    # jax counts a miss when it writes the entry, so a program that
    # compiled under the cache's floor (`_compile_cache.py`: 0 but on the
    # CPU, where jax's second stays) is neither
    "/jax/compilation_cache/cache_misses": "misses",
}
_compile_totals: dict[str, float] = dict.fromkeys(
    (*_COMPILE_STAGES.values(), "cache_retrieval", "programs",
     *_CACHE_RESULTS.values()), 0)
_compile_lock = threading.Lock()
_compile_watched = False
_compile_open = threading.local()  # .spans: this thread's [start, end]s


def _own_seconds(duration: float) -> float:
    """jax reports a stage when it ends, and stages nest: a jitted
    function called while another is traced reports its own trace
    first, a helper jitted inside a lowering rule its own three. The
    part of this report that no earlier report of this thread already
    covers, so that the stages sum to no more than the wall clock."""
    end = time.monotonic()
    start = end - duration
    spans = _compile_open.__dict__.setdefault("spans", [])
    own = duration
    # reports come in order of their ends, so what began inside this one
    # is a suffix (100 us of grace: both starts are read a little late)
    while spans and spans[-1][0] >= start - 1e-4:
        s0, e0 = spans.pop()
        own -= e0 - s0
    spans.append((start, end))
    return max(own, 0.0)


def watch_compiles() -> None:
    """Install, once per process, the `jax.monitoring` listeners that
    sum what jax spends tracing, lowering and compiling (or loading
    from the persistent cache), and how often that cache hit or missed:
    `compile_totals()`, `jax_compile_seconds_total{stage}`,
    `jax_compile_cache_total{result}`. They fire only when jax
    compiles, never on a step path. Called by whoever is about to
    compile (the serve engine, the train step builder)."""
    global _compile_watched
    with _compile_lock:
        if _compile_watched:
            return
        _compile_watched = True
    from jax import monitoring

    from ray_tpu.util.metrics import Counter

    seconds = Counter(
        "jax_compile_seconds_total",
        "Seconds jax spent per compile stage in this process: trace, "
        "lower, backend_compile (XLA, or the cache load on a hit), each "
        "without what nests inside it; cache_retrieval is the part of "
        "backend_compile spent reading the persistent cache",
        tag_keys=("stage",))
    cache = Counter(
        "jax_compile_cache_total",
        "Persistent compile cache lookups that found (hits) or wrote "
        "(misses) an entry", tag_keys=("result",))

    def on_duration(event: str, duration: float, **_):
        if event == _CACHE_RETRIEVAL:
            stage = "cache_retrieval"
        else:
            stage = _COMPILE_STAGES.get(event)
            if stage is None:
                return
            duration = _own_seconds(duration)
        with _compile_lock:
            _compile_totals[stage] += duration
            # one `backend_compile` report a program compiled or loaded
            _compile_totals["programs"] += stage == "backend_compile"
        seconds.inc(duration, tags={"stage": stage})

    def on_event(event: str, **_):
        result = _CACHE_RESULTS.get(event)
        if result is not None:
            with _compile_lock:
                _compile_totals[result] += 1
            cache.inc(tags={"result": result})

    monitoring.register_event_duration_secs_listener(on_duration)
    monitoring.register_event_listener(on_event)


def compile_totals(since: dict[str, float] | None = None) -> dict[str, float]:
    """Process-wide sums since `watch_compiles()`: seconds under
    `trace`, `lower`, `backend_compile`, `cache_retrieval`; counts under
    `programs` (the `backend_compile` reports), `hits`, `misses`. With
    `since`, an earlier reading: what was added after it."""
    with _compile_lock:
        totals = dict(_compile_totals)
    if since is not None:
        totals = {k: v - since[k] for k, v in totals.items()}
    return totals


def dump(filename: str):
    """Write this process's trace to `filename`: the merged cluster
    timeline when a runtime is initialized, else the fallback log
    (bench scripts without a cluster)."""
    rt = _runtime()
    if rt is not None and hasattr(rt, "timeline"):
        return rt.timeline(filename)
    return _fallback_log.chrome_trace(filename)
