"""The serve engine's step loop and start-up, measured from inside
(ISSUE 25): phase annotations in the device profiler's trace, the
always-on phase / step / bytes-to-host counters of `engine.stats()`, the
start-up split, and jax's own compile account."""

import glob
import os
import subprocess
import sys
import threading
import time

import jax
import jax.numpy as jnp
import pytest

from ray_tpu.util import tracing

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

STEP_PARENTS = ("llm.step.decode", "llm.step.prefill")
INSIDE_A_STEP = ("llm.prepare", "llm.dispatch", "llm.fetch", "llm.commit",
                 "llm.emit", "llm.bookkeep")
BETWEEN_STEPS = ("llm.schedule", "llm.idle")
VOCAB_PADDED = 128


def _config(**overrides):
    from ray_tpu.models import gpt2
    from ray_tpu.serve.llm import EngineConfig

    cfg = gpt2.GPT2Config(
        vocab_size=100, n_layer=2, n_head=2, n_embd=64, block_size=64,
        vocab_pad_multiple=VOCAB_PADDED, dtype=jnp.float32, remat=False)
    kw = dict(model="gpt2", model_config=cfg, block_size=8, num_blocks=64,
              max_model_len=64, max_batch_size=4, prefill_chunk_size=8,
              seed=0)
    kw.update(overrides)
    return EngineConfig(**kw)


@pytest.fixture(scope="module")
def engine():
    from ray_tpu.serve.llm import LLMEngine

    e = LLMEngine(_config())
    e.warmup()
    return e


class _Capture:
    """A CPU profiler capture as the benchmark takes it on the chip:
    host events on, Python call tracer off. `.events` after the block:
    (plane, line, name, start_ns, end_ns)."""

    def __init__(self, out_dir):
        self.out_dir = str(out_dir)

    def __enter__(self):
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        jax.profiler.start_trace(self.out_dir, profiler_options=options)
        return self

    def __exit__(self, *exc):
        jax.profiler.stop_trace()
        path, = glob.glob(os.path.join(
            self.out_dir, "plugins", "profile", "*", "*.xplane.pb"))
        data = jax.profiler.ProfileData.from_file(path)
        self.events = [
            (plane.name, line.name, ev.name, ev.start_ns,
             ev.start_ns + ev.duration_ns)
            for plane in data.planes for line in plane.lines
            for ev in line.events]


def _logged_names() -> set[str]:
    return {e["name"] for e in tracing._fallback_log.chrome_trace()}


# ---------------------------------------------------------------------------
# (a) the annotations, in the profiler's own trace
# ---------------------------------------------------------------------------

def test_every_phase_shows_in_a_profile_and_nests(tmp_path):
    from ray_tpu.serve.llm.config import SamplingParams
    from ray_tpu.serve.llm.deployment import LLMServer

    server = LLMServer(_config(), warmup=False)
    try:
        # compile outside the capture: a chunked prefill, then decode
        server.engine.generate(list(range(1, 20)),
                               SamplingParams(max_tokens=3), timeout=120)
        with _Capture(tmp_path) as cap:
            time.sleep(0.02)  # the loop idles
            final = server.engine.generate(
                list(range(2, 21)), SamplingParams(max_tokens=4),
                timeout=120)
            time.sleep(0.02)
        assert final["finish_reason"] == "length"
    finally:
        server.shutdown_engine()
        server._loop.join(timeout=10)
    assert not server._loop.is_alive()

    llm = [e for e in cap.events if e[2].startswith("llm.")]
    names = {e[2] for e in llm}
    assert set(STEP_PARENTS + INSIDE_A_STEP + BETWEEN_STEPS) <= names
    assert len([e for e in llm if e[2] in STEP_PARENTS]) >= 6
    # all in the host plane, on the line of the one thread that steps
    assert {e[0] for e in llm} == {"/host:CPU"}
    assert len({e[1] for e in llm}) == 1
    # every phase of a step lies inside a step, the others outside all
    # (a step the capture's edges cut leaves its phases without a parent)
    steps = [(s, t) for _, _, n, s, t in llm if n in STEP_PARENTS]
    first, last = min(a for a, _ in steps), max(b for _, b in steps)
    for _, _, name, s, t in llm:
        if s < first or t > last:
            continue
        inside = any(a <= s and t <= b for a, b in steps)
        if name in INSIDE_A_STEP:
            assert inside, name
        elif name in BETWEEN_STEPS:
            assert not any(a < t and s < b for a, b in steps), name
    # the loop's phases go to the profile only, never to the span log
    assert not _logged_names() & set(
        STEP_PARENTS + INSIDE_A_STEP + BETWEEN_STEPS)


# ---------------------------------------------------------------------------
# (b) the counters
# ---------------------------------------------------------------------------

def test_phase_seconds_sum_to_the_loops_wall_time():
    from ray_tpu.models import gpt2
    from ray_tpu.serve.llm import LLMEngine
    from ray_tpu.serve.llm.config import SamplingParams

    # steps of a few ms, so that what lies between the phases (some tens
    # of microseconds a step) stays well inside the tolerance
    model = gpt2.GPT2Config(
        vocab_size=1000, n_layer=4, n_head=4, n_embd=256, block_size=64,
        vocab_pad_multiple=1024, dtype=jnp.float32, remat=False)
    engine = LLMEngine(_config(model_config=model,
                               enable_prefix_cache=False))

    def drive():
        streams = [engine.add_request(list(range(1, 30)),
                                      SamplingParams(max_tokens=12))
                   for _ in range(3)]
        steps = 0
        while engine.has_work():
            assert engine.step()
            steps += 1
        assert all(s.final()["finish_reason"] == "length" for s in streams)
        return steps

    drive()  # compiles every program the measured pass runs
    before = engine.stats()
    t0 = time.perf_counter()
    steps = drive()
    wall = time.perf_counter() - t0
    after = engine.stats()
    spent = {k: after["step_phase_seconds"][k] - v
             for k, v in before["step_phase_seconds"].items()}
    assert set(spent) == {"schedule", "prepare", "dispatch", "fetch",
                          "commit", "emit", "bookkeep", "idle", "release",
                          "yield"}
    assert all(v >= 0 for v in spent.values())
    assert spent["idle"] == 0  # only the deployment's loop idles
    assert spent["yield"] == 0  # no swap and no abort wanted the engine
    assert sum(spent.values()) == pytest.approx(wall, rel=0.05)
    counted = sum(after["steps"][k] - before["steps"][k]
                  for k in ("decode", "prefill"))
    assert counted == steps


def test_steps_and_bytes_match_what_was_driven(engine):
    from ray_tpu.serve.llm.config import SamplingParams

    before = engine.stats()
    # 20 prompt tokens in chunks of 8: three prefill steps, the last one
    # yields the first token; four decode steps of one lane yield the rest
    final = engine.generate(list(range(3, 23)),
                            SamplingParams(max_tokens=5), drive=True)
    assert final["num_generated"] == 5
    after = engine.stats()
    steps = {k: after["steps"][k] - before["steps"][k]
             for k in ("decode", "prefill")}
    assert steps == {"decode": 4, "prefill": 3}
    fetched = {k: after["d2h_bytes"][k] - before["d2h_bytes"][k]
               for k in ("decode", "prefill")}
    # a step fetches its sampled token(s) and its f32 logits rows
    row = 4 + 4 * VOCAB_PADDED
    assert fetched == {"decode": 4 * row, "prefill": 3 * row}
    # four lanes at once: one decode step fetches four rows
    streams = [engine.add_request([5, 6, 7], SamplingParams(max_tokens=2))
               for _ in range(4)]
    for _ in range(4):
        engine.step()  # four one-chunk prefills
    mid = engine.stats()
    assert engine.step()
    assert all(s.final() is not None for s in streams)
    assert engine.stats()["d2h_bytes"]["decode"] \
        - mid["d2h_bytes"]["decode"] == 4 * row
    # and the metrics page carries the same total
    from ray_tpu.util.metrics import prometheus_text

    line = [ln for ln in prometheus_text().splitlines()
            if ln.startswith("serve_llm_d2h_bytes_total{")
            and 'kind="decode"' in ln]
    assert line, "serve_llm_d2h_bytes_total{kind=decode} not exposed"


def _tiny_engine(model, **overrides):
    from ray_tpu.serve.llm import EngineConfig, LLMEngine

    if model == "gpt2":
        return LLMEngine(_config(enable_prefix_cache=False, **overrides))
    # the routed-expert block through the llama path: its programs hand
    # the host the experts' pairs beside the logits
    return LLMEngine(EngineConfig(
        model="llama", preset="olmoe_tiny", block_size=8, num_blocks=64,
        max_model_len=64, max_batch_size=4, prefill_chunk_size=8, seed=0,
        enable_prefix_cache=False, **overrides))


def _launch_spies(runner):
    """Every jitted call the runner makes from here on, by program:
    [(leaves that are `jax.Array`s, leaves that are not, the bytes of
    those), ...]."""
    seen = {}
    for name in ("prefill", "chunk", "decode", "verify"):
        inner = getattr(runner, f"_{name}_jit")
        calls = seen[name] = []

        def call(*args, _inner=inner, _calls=calls):
            leaves = jax.tree.leaves(args)
            on_host = [x for x in leaves if not isinstance(x, jax.Array)]
            _calls.append((len(leaves) - len(on_host), len(on_host),
                           sum(x.nbytes for x in on_host)))
            return _inner(*args)
        call._cache_size = inner._cache_size
        setattr(runner, f"_{name}_jit", call)
    return seen


def _by_kind_rose(after, before, what):
    return {kind: {k: v - before[what][kind][k] for k, v in n.items()}
            for kind, n in after[what].items() if isinstance(n, dict)}


@pytest.mark.parametrize("model", ["gpt2", "olmoe"])
def test_launch_and_fetch_accounts_count_what_was_run(model):
    from ray_tpu.serve.llm.config import SamplingParams

    eng = _tiny_engine(model)
    runner = eng.runner
    eng.warmup()
    # what warm-up launched and fetched is no step's
    st = eng.stats()
    assert set(st["launch"]) == {"resident_leaves", *st["fetch"]} \
        == {"resident_leaves", "prefill", "decode", "verify"}
    for what in ("launch", "fetch"):
        for kind in ("prefill", "decode", "verify"):
            assert st[what][kind] == dict.fromkeys(st[what][kind], 0), \
                (what, kind)
    # the leaves every call hands over already on the device
    assert st["launch"]["resident_leaves"] == len(jax.tree.leaves((
        runner.params, runner.k_pages, runner.v_pages, runner.slot_tokens,
        runner.state)))
    seen = _launch_spies(runner)

    def drive():
        before = eng.stats()
        streams = [eng.add_request(list(range(2, 2 + n)),
                                   SamplingParams(max_tokens=4))
                   for n in (5, 19, 11)]
        while eng.has_work():
            assert eng.step()
        assert all(s.final()["finish_reason"] == "length" for s in streams)
        after = eng.stats()
        rose = {what: _by_kind_rose(after, before, what)
                for what in ("launch", "fetch")}
        rose["steps"] = {k: v - before["steps"][k]
                         for k, v in after["steps"].items()}
        for phase in ("fetch", "dispatch"):
            rose[phase + "_phase"] = after["step_phase_seconds"][phase] \
                - before["step_phase_seconds"][phase]
        return rose

    first = drive()
    launch = first["launch"]
    # a call a program run, by kind as `steps` has them
    assert launch["prefill"]["calls"] == first["steps"]["prefill"] \
        == len(seen["prefill"]) + len(seen["chunk"]) > 0
    assert launch["decode"]["calls"] == first["steps"]["decode"] \
        == len(seen["decode"]) > 0
    assert launch["verify"] == dict.fromkeys(launch["verify"], 0)
    # what each call handed the runtime: the resident trees' leaves (the
    # one number), and the numpy arguments its launch passes
    for kind, calls in (("prefill", seen["prefill"] + seen["chunk"]),
                        ("decode", seen["decode"])):
        assert {d for d, _, _ in calls} == {st["launch"]["resident_leaves"]}
        assert launch[kind]["host_arrays"] == sum(h for _, h, _ in calls)
        assert launch[kind]["host_bytes"] == sum(b for _, _, b in calls) \
            > 4 * launch[kind]["host_arrays"]
    # tokens, slots, positions, the kind's tables, three sampling fields
    # and the step's count travel as one array, the launch's pack (PR 56:
    # they were eight arrays, ten of a chunk's program): one transfer
    assert {h for _, h, _ in seen["decode"]} == {1}
    assert {h for _, h, _ in seen["chunk"]} == {1}
    assert {h for _, h, _ in seen["prefill"]} <= {1}
    for kind in ("prefill", "decode"):
        assert launch[kind]["host_arrays"] == launch[kind]["calls"]
    # the jitted calls alone are part of the `dispatch` phase
    assert 0 < sum(n["wall_s"] for n in launch.values()) \
        < first["dispatch_phase"]
    # the wait, the copy and the rows put back in order are parts of the
    # `fetch` phase, and nearly all of it
    parts = sum(sum(n.values()) for n in first["fetch"].values())
    assert 0.5 * first["fetch_phase"] < parts <= first["fetch_phase"]
    assert all(first["fetch"][kind][part] > 0
               for kind in ("prefill", "decode")
               for part in ("wait_s", "copy_s"))
    # a prompt's one row has no order to be put back in
    assert first["fetch"]["decode"]["order_s"] > 0 \
        == first["fetch"]["prefill"]["order_s"]
    # the same requests again: every count repeats exactly
    again = drive()
    counts = ("calls", "host_arrays", "host_bytes")
    assert {kind: {k: n[k] for k in counts}
            for kind, n in again["launch"].items()} \
        == {kind: {k: n[k] for k in counts} for kind, n in launch.items()}
    assert again["steps"] == first["steps"]
    assert eng.stats()["launch"]["resident_leaves"] \
        == st["launch"]["resident_leaves"]


def test_fetch_parts_sum_to_no_more_than_the_fetch_phase(engine):
    from ray_tpu.serve.llm.config import SamplingParams

    before = engine.stats()
    for n in (7, 20):
        engine.generate(list(range(3, 3 + n)), SamplingParams(max_tokens=6),
                        drive=True)
    after = engine.stats()
    rose = _by_kind_rose(after, before, "fetch")
    parts = sum(sum(n.values()) for n in rose.values())
    phase = after["step_phase_seconds"]["fetch"] \
        - before["step_phase_seconds"]["fetch"]
    assert 0.5 * phase < parts <= phase
    assert rose["verify"] == {"wait_s": 0, "copy_s": 0, "order_s": 0}


def test_a_verify_dispatch_writes_the_same_two_records():
    from ray_tpu.serve.llm import SpeculativeConfig
    from ray_tpu.serve.llm.config import SamplingParams

    eng = _tiny_engine("gpt2", speculative=SpeculativeConfig(
        method="ngram", num_draft_tokens=3))
    seen = _launch_spies(eng.runner)
    eng.generate([5, 6, 7, 5, 6, 7, 5, 6], SamplingParams(max_tokens=8),
                 drive=True)
    st = eng.stats()
    assert st["launch"]["verify"]["calls"] == len(seen["verify"]) > 0
    # the parameters and the pools: a verify program carries no ids, no
    # state
    assert {d for d, _, _ in seen["verify"]} == {len(jax.tree.leaves((
        eng.runner.params, eng.runner.k_pages, eng.runner.v_pages)))}
    assert st["launch"]["verify"]["host_arrays"] \
        == sum(h for _, h, _ in seen["verify"])
    assert st["launch"]["verify"]["host_bytes"] \
        == sum(b for _, _, b in seen["verify"])
    assert 0 < st["launch"]["verify"]["wall_s"] \
        < st["step_phase_seconds"]["dispatch"]
    assert st["fetch"]["verify"]["wait_s"] > 0
    assert st["fetch"]["verify"]["order_s"] == 0
    parts = sum(sum(n.values()) for n in st["fetch"].values())
    assert parts <= st["step_phase_seconds"]["fetch"]


# ---------------------------------------------------------------------------
# (c) start-up
# ---------------------------------------------------------------------------

def test_startup_split(engine):
    st = engine.stats()
    up = st["startup_seconds"]
    assert set(up) == {"init_params", "build_runner", "init_compile",
                       "warmup", "warmup_trace", "warmup_lower",
                       "warmup_compile"}
    assert all(v >= 0 for v in up.values())
    assert up["init_params"] > 0 and up["build_runner"] > 0
    # warm-up traced, lowered and compiled (or loaded) its programs, and
    # the three are parts of its wall time, not more than it
    assert min(up["warmup_trace"], up["warmup_lower"],
               up["warmup_compile"]) > 0
    assert up["warmup_trace"] + up["warmup_lower"] + up["warmup_compile"] \
        <= up["warmup"]
    assert set(st["warmup_cache"]) == {"hits", "misses"}
    assert all(v >= 0 for v in st["warmup_cache"].values())


def test_params_handed_in_cost_no_init():
    from ray_tpu.models import gpt2
    from ray_tpu.serve.llm import LLMEngine

    config = _config()
    params = gpt2.init_gpt2(jax.random.PRNGKey(1), config.model_config)
    e = LLMEngine(config, params=params)
    up = e.stats()["startup_seconds"]
    assert up["init_params"] == 0.0 and up["build_runner"] > 0
    assert up["warmup"] == 0.0  # not warmed up yet


def test_init_cache_counts_what_the_start_compiled():
    """`init_cache` and `init_compile` are to `init_params` +
    `build_runner` what `warmup_cache` and `warmup_compile` are to
    warm-up: a configuration this process has not made yet compiles its
    init (one program, `init_gpt2`) and the runner's casts; the same
    configuration again compiles nothing."""
    import dataclasses

    from ray_tpu.serve.llm import LLMEngine

    config = _config()
    config = dataclasses.replace(config, model_config=dataclasses.replace(
        config.model_config, n_embd=48))
    st = LLMEngine(config).stats()
    up, cache = st["startup_seconds"], st["init_cache"]
    assert set(cache) == {"programs", "hits", "misses"}
    assert cache["programs"] >= 1
    # under jax's floor (the CPU keeps it) a compile is neither
    assert 0 <= cache["hits"] + cache["misses"] <= cache["programs"]
    assert 0 < up["init_compile"] < up["init_params"] + up["build_runner"]
    assert st["warmup_cache"] == {"hits": 0, "misses": 0}

    again = LLMEngine(config).stats()
    assert again["init_cache"] == {"programs": 0, "hits": 0, "misses": 0}
    assert again["startup_seconds"]["init_compile"] == 0.0


def test_compile_stages_nest_without_counting_twice():
    """A jitted function called while another is traced reports its own
    trace: the listener counts each second once."""
    tracing.watch_compiles()
    salt = float(time.time_ns() % 1000)

    @jax.jit
    def inner(x):
        return jnp.tanh(x) * salt

    @jax.jit
    def outer(x):
        for _ in range(20):
            x = inner(x) + jnp.sin(x)
        return x

    x = jnp.ones((8, 8))
    before = tracing.compile_totals()
    t0 = time.perf_counter()
    outer(x).block_until_ready()
    wall = time.perf_counter() - t0
    spent = tracing.compile_totals(since=before)
    assert spent["trace"] > 0 and spent["lower"] > 0
    assert spent["backend_compile"] > 0
    assert spent["programs"] == 1  # `inner` is inlined into `outer`
    assert spent["trace"] + spent["lower"] + spent["backend_compile"] \
        <= wall
    from ray_tpu.util.metrics import prometheus_text

    assert 'jax_compile_seconds_total{stage="trace"}' in prometheus_text()


# ---------------------------------------------------------------------------
# (d), (e) the span API
# ---------------------------------------------------------------------------

def test_span_shows_in_a_profile_and_in_the_log(tmp_path):
    with _Capture(tmp_path) as cap:
        with tracing.span("phases-outer", category="test"):
            with tracing.span("phases-inner", category="test"):
                time.sleep(0.001)
    spans = {e[2]: e for e in cap.events
             if e[2] in ("phases-outer", "phases-inner")}
    assert set(spans) == {"phases-outer", "phases-inner"}
    assert spans["phases-outer"][0] == "/host:CPU"
    assert spans["phases-outer"][3] <= spans["phases-inner"][3]
    assert spans["phases-inner"][4] <= spans["phases-outer"][4]
    assert {"phases-outer", "phases-inner"} <= _logged_names()


def test_annotate_is_a_profile_event_only(tmp_path):
    with _Capture(tmp_path) as cap:
        with tracing.annotate("phases-annotated"):
            pass
    assert "phases-annotated" in {e[2] for e in cap.events}
    assert "phases-annotated" not in _logged_names()


def test_annotate_imports_no_jax():
    code = ("import sys\n"
            "from ray_tpu.util import tracing\n"
            "with tracing.annotate('x'), tracing.span('y'):\n"
            "    pass\n"
            "clock = tracing.PhaseClock('p.', ('a',))\n"
            "with clock.phase('a'):\n"
            "    pass\n"
            "assert clock.seconds['a'] > 0\n"
            "assert 'jax' not in sys.modules, 'jax was imported'\n")
    done = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                          capture_output=True, text=True, timeout=120)
    assert done.returncode == 0, done.stderr


# ---------------------------------------------------------------------------
# profiler_capture: a capture that did not happen must say so
# ---------------------------------------------------------------------------

class _Chip:
    platform = "tpu"


def test_profiler_capture_arms_host_events_without_python_tracer(
        tmp_path, monkeypatch):
    seen = {}

    class Armed:
        def __enter__(self):
            seen["entered"] = True

        def __exit__(self, *exc):
            seen["left"] = True

    def fake_trace(log_dir, profiler_options=None, **_):
        seen["dir"] = log_dir
        seen["python"] = profiler_options.python_tracer_level
        seen["host"] = profiler_options.host_tracer_level
        return Armed()

    monkeypatch.setattr(jax, "devices", lambda *a: [_Chip()])
    monkeypatch.setattr(jax.profiler, "trace", fake_trace)
    out = str(tmp_path / "prof")
    with tracing.profiler_capture(out) as captured:
        assert captured == out and seen["entered"]
    assert seen == {"dir": out, "python": 0, "host": 2, "entered": True,
                    "left": True}
    assert "profiler.capture" in _logged_names()


@pytest.mark.parametrize("fails_at", ["start", "stop"])
def test_profiler_capture_failure_raises_on_a_chip(tmp_path, monkeypatch,
                                                   fails_at):
    class Broken:
        def __enter__(self):
            if fails_at == "start":
                raise RuntimeError("profiler did not start")

        def __exit__(self, *exc):
            if fails_at == "stop":
                raise RuntimeError("profiler did not stop")

    monkeypatch.setattr(jax, "devices", lambda *a: [_Chip()])
    monkeypatch.setattr(jax.profiler, "trace", lambda *a, **k: Broken())
    ran = []
    with pytest.raises(RuntimeError, match="profiler did not " + fails_at):
        with tracing.profiler_capture(str(tmp_path / "prof")):
            ran.append(1)
    assert ran == ([] if fails_at == "start" else [1])


def test_phase_clock_is_shared_by_engine_and_runner(engine):
    assert engine.phases is engine.runner.phases
    # driven from two threads, steps still serialize and every one counts
    from ray_tpu.serve.llm.config import SamplingParams

    before = engine.stats()["steps"]
    streams = [engine.add_request([9, 8, 7, 6], SamplingParams(max_tokens=6))
               for _ in range(2)]
    done = []

    def drive():
        n = 0
        while engine.has_work():
            n += bool(engine.step())
        done.append(n)

    threads = [threading.Thread(target=drive) for _ in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    assert not any(t.is_alive() for t in threads)
    assert all(s.final() is not None for s in streams)
    after = engine.stats()["steps"]
    assert sum(after.values()) - sum(before.values()) == sum(done)


# the tiny engine's warm-up on the parent commit (PR 52's tree, jax 0.9.0,
# after `jax.clear_caches()`): jax's own reports of a trace, a lowering and
# a compile, counted. Four traces fewer since PR 62 (1,160 before):
# `threefry_2x32`, `_threefry_seed`, `ravel` and one `bitwise_and`, helpers
# of the sampling key that `init_gpt2`, one traced program now, has traced
# by the time warm-up asks for them
WARMUP_REPORTS = {"jaxpr_trace_duration": 1156,
                  "jaxpr_to_mlir_module_duration": 5,
                  "backend_compile_duration": 5}


def test_warmup_traces_lowers_and_compiles_as_often_as_the_parent():
    """A warm-up guard: `warmup()` of the tiny engine makes jax report as
    many traces, lowerings and compiles as it did on the parent commit. It
    catches a change that traces a program twice or adds a jitted helper
    to every trace. It would NOT have caught PR 53, whose counts were the
    parent's and whose traces only ran slower, in a replica on the chip's
    host: that shows in `warmup_trace_lower_s` of a traced cell run alone
    (PERF.md §6, PR 54). Last in this file: it empties jax's caches."""
    import collections

    from jax import monitoring

    from ray_tpu.serve.llm import LLMEngine

    seen = collections.Counter()

    def listen(event, duration, **_):
        seen[event.rsplit("/", 1)[-1]] += 1

    jax.clear_caches()
    e = LLMEngine(_config())
    monitoring.register_event_duration_secs_listener(listen)
    try:
        programs = e.warmup()
    finally:
        monitoring.unregister_event_duration_listener(listen)
    assert programs == WARMUP_REPORTS["backend_compile_duration"]
    assert {k: seen[k] for k in WARMUP_REPORTS} == WARMUP_REPORTS
