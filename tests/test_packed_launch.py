"""A serve launch's host arguments travel as one int32 array (PR 56):
`launch_*` fill the fields of a fresh pack where they filled eight to ten
arrays, the program takes it apart by static slices (`runner.pack_layout`).

(a) what the four launches sample and the logits they return are the
    parent's to the bit, on gpt2, OLMoE, a family with two kinds of KV
    layer and two that carry state (pinned from commit 2151644, where each
    argument was an array of its own, by `_record` below);
(b) every field comes back from the program's slices bit for bit,
    temperature and top-p among them;
(c) a launch hands the runtime one host array, whatever the kind;
(d) a launch's pack is its own: a later launch's fill cannot change the
    result of one still in flight.
"""

import functools
import hashlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.serve.llm.runner import (
    PACK_FLOATS,
    DecodeItem,
    ModelRunner,
    adapters,
    pack_layout,
)

PINS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "serve_launch_pr56.json")

# family -> (preset, kinds of KV layer, whether `verify` runs on it: the
# verify program carries no recurrent state)
FAMILIES = {"gpt2": ("tiny", 1, True),
            "llama": ("olmoe_tiny", 1, True),
            "mimo_v2": ("tiny", 2, True),
            "nemotron_h": ("tiny", 1, False),
            "lfm2": ("tiny", 1, False)}
SAMPLING = {"greedy": (0.0, 0, 1.0), "seeded": (0.7, 5, 0.95)}
CASES = [(family, program) for family, (_, _, verifies) in FAMILIES.items()
         for program in ("prefill", "chunk", "decode")
         + ("verify",) * verifies]


def _runner(family, *, seed=3):
    """A tiny runner on real parameters: pages of 4, chunks of 16, four
    lanes, two drafts where the family can verify."""
    preset, kinds, verifies = FAMILIES[family]
    adapter = adapters()[family]
    cfg = adapter.presets[preset]()
    params = adapter.init_fn(jax.random.PRNGKey(1), cfg)
    return ModelRunner(adapter, cfg, params, block_size=4,
                       num_blocks=[52] * kinds, max_model_len=64,
                       max_batch_size=4, prefill_chunk_size=16,
                       num_draft_tokens=2 * verifies, sample_seed=seed)


@functools.lru_cache(maxsize=None)
def _shared_runner(family):
    return _runner(family)


def _as_new(runner):
    """`runner` with its pools, its lanes' state, the ids it keeps on the
    device and its count of steps as a new runner has them: its programs
    stay compiled, which is most of what a case costs."""
    runner.reset_cache()
    runner.slot_tokens = jnp.zeros_like(runner.slot_tokens)
    runner._step_counter = 0
    return runner


def _pages(runner, first):
    """Sixteen pages from `first` on, in every kind's pool."""
    return [list(range(first, first + 16)) for _ in runner.layouts]


def _launch(runner, program, sampling):
    """`program` launched once, after what it needs before it, and what
    `collect` then returns nowhere yet: (the launch, or verify's results).
    Lane slot 0 holds a prompt of 13 tokens (a prompt's program, then a
    chunk's) on pages 1-16."""
    long = _pages(runner, 1)
    prompt = list(range(5, 18))
    if program == "prefill":
        return runner.launch_prefill(prompt, long, *sampling, slot=0)
    runner.collect(runner.launch_prefill(prompt[:8], long, 0.0, slot=0))
    if program == "chunk":
        return runner.launch_chunk(prompt[8:], 8, long, *sampling, slot=0)
    tok, _ = runner.collect(runner.launch_chunk(prompt[8:], 8, long, 0.0,
                                                slot=0))
    if program == "verify":
        return runner.verify(tok, 13, [tok, 9], long, *sampling)
    # the long lane's id is still on the device; two shorter lanes of
    # different lengths, one of them greedy; one padded row in the bucket
    for slot, first, n in ((1, 17, 3), (2, 33, 6)):
        runner.collect(runner.launch_prefill(
            prompt[:n], _pages(runner, first), 0.0, slot=slot))
    return runner.launch_decode([
        DecodeItem(11, 3, _pages(runner, 17), 0.0, slot=1),
        DecodeItem(-1, 13, long, *sampling, slot=0),
        DecodeItem(7, 6, _pages(runner, 33), *sampling, slot=2)])


def _run(runner, program, sampling):
    out = _launch(runner, program, sampling)
    toks, logits = out if program == "verify" else runner.collect(out)
    return [int(t) for t in np.atleast_1d(toks)], np.asarray(logits)


def _pin(toks, logits):
    return {"tokens": toks, "logits_shape": list(logits.shape),
            "logits_sha256": hashlib.sha256(
                np.ascontiguousarray(logits).tobytes()).hexdigest()}


def _record():  # python -c "from tests import test_packed_launch as t; t._record()"
    pins = {f"{family}.{program}.{name}": _pin(*_run(
        _runner(family), program, sampling))
        for family, program in CASES for name, sampling in SAMPLING.items()}
    with open(PINS, "w") as f:
        json.dump(pins, f, indent=1, sort_keys=True)
        f.write("\n")


# ------------------------------------------------- (a) the parent's results


@pytest.mark.parametrize("name", list(SAMPLING))
@pytest.mark.parametrize("family,program", CASES)
def test_a_launch_gives_what_it_gave_at_the_parent(family, program, name):
    with open(PINS) as f:
        pinned = json.load(f)[f"{family}.{program}.{name}"]
    toks, logits = _run(_as_new(_shared_runner(family)), program,
                        SAMPLING[name])
    assert logits.dtype == np.float32 and np.isfinite(logits).all()
    assert _pin(toks, logits) == pinned


# ------------------------------------------------------- (b) the pack itself


def _shapes_runner(family, drafts=0):
    """A tiny runner on parameter shapes alone: the layout needs no more."""
    preset, kinds, _ = FAMILIES[family]
    adapter = adapters()[family]
    cfg = adapter.presets[preset]()
    params = jax.eval_shape(
        lambda k: adapter.resident_fn(adapter.init_fn(k, cfg), cfg),
        jax.random.PRNGKey(1))
    return ModelRunner(adapter, cfg, params, block_size=4,
                       num_blocks=[40] * kinds, max_model_len=64,
                       max_batch_size=4, prefill_chunk_size=16,
                       num_draft_tokens=drafts)


@pytest.mark.parametrize("family", ["gpt2", "mimo_v2"])
@pytest.mark.parametrize("kind,bucket", [
    ("prefill", 16), ("prefill", 64), ("chunk", 8), ("chunk", 16),
    ("decode", 1), ("decode", 4), ("verify", 3), ("verify", 5)])
def test_every_field_comes_back_from_the_programs_slices(family, kind,
                                                         bucket):
    r = _shapes_runner(family, drafts=bucket - 1 if kind == "verify" else 0)
    kinds, blocks = len(r.layouts), r.max_blocks_per_seq
    assert kinds == FAMILIES[family][1]
    size, layout = pack_layout(kind, bucket, kinds, blocks)
    host, fields = r._pack(kind, bucket)
    assert host.shape == (size,) and host.dtype == np.int32
    assert not host.any()  # token 0, the null page, greedy
    # every element of the array is one field's, and no field lies on
    # another: each filled with values of its own, all still there after
    assert sum(view.size for view in fields.values()) == size
    rng = np.random.default_rng(bucket)
    want = {}
    for name, view in fields.items():
        assert np.shares_memory(view, host), name  # filled where it lies
        assert view.shape == layout[name][1], name
        assert view.dtype == (np.float32 if name in PACK_FLOATS
                              else np.int32), name
        want[name] = rng.integers(-2 ** 31, 2 ** 31, view.shape).astype(
            np.int32).view(view.dtype)
    for name in PACK_FLOATS:  # values that do not round, then off and off
        want[name].flat[:] = np.resize(
            np.float32([0.7, 0.0, 1.0, 0.95, 1e-7]), want[name].size)
    for name, view in fields.items():
        view[...] = want[name]
    for name, view in fields.items():
        assert np.array_equal(view.view(np.int32),
                              want[name].view(np.int32)), name
    # the program finds its bucket from the array's length alone and
    # slices the same fields out, a float by its bits
    got = jax.jit(lambda h: r._unpacked(kind, h))(host)
    assert set(got) == set(want)
    for name, value in want.items():
        out = np.asarray(got[name])
        assert out.dtype == value.dtype and out.shape == value.shape, name
        assert np.array_equal(out.view(np.int32), value.view(np.int32)), name
    assert np.float32(0.7) in np.asarray(got["temps"])
    # a field that is one a kind of KV layer leads with the kinds
    for name in ("page_ids", "table", "tables", "block_ids"):
        if name in want:
            assert want[name].shape[0] == kinds, name


def test_the_layout_is_worked_out_once_a_bucket():
    before = pack_layout.cache_info()
    r = _shapes_runner("gpt2")
    for _ in range(3):
        r._pack("decode", 2)
    after = pack_layout.cache_info()
    assert after.misses - before.misses <= 1
    assert after.hits - before.hits >= 2


# --------------------------------------------------------- (c) one transfer

# compiled_signatures() after warmup() at the parent commit (2151644), on
# the same engines: a program a bucket, as before
SIGNATURES_AT_THE_PARENT = {"gpt2": 6, "mimo_v2": 5, "nemotron_h": 5}


@pytest.mark.parametrize("family", list(SIGNATURES_AT_THE_PARENT))
def test_a_launch_hands_the_runtime_one_host_array(family):
    from ray_tpu.serve.llm import EngineConfig, LLMEngine, SamplingParams

    spec = {"speculative": {"num_draft_tokens": 2}} if family == "gpt2" \
        else {}
    eng = LLMEngine(EngineConfig(
        model=family, preset="tiny", block_size=4, num_blocks=96,
        max_model_len=64, max_batch_size=4, prefill_chunk_size=8, seed=0,
        **spec))
    eng.warmup()
    assert eng.runner.compiled_signatures() \
        == SIGNATURES_AT_THE_PARENT[family]
    handed = []  # the host leaves of every jitted call
    for name in ("prefill", "chunk", "decode", "verify"):
        inner = getattr(eng.runner, f"_{name}_jit")

        def call(*args, _inner=inner):
            handed.append([x for x in jax.tree.leaves(args)
                           if not isinstance(x, jax.Array)])
            return _inner(*args)
        call._cache_size = inner._cache_size
        setattr(eng.runner, f"_{name}_jit", call)
    # a repeating prompt, so that the n-gram proposer drafts
    streams = [eng.add_request(prompt, SamplingParams(max_tokens=6))
               for prompt in ([3, 4, 5] * 4, list(range(2, 21)), [7, 8])]
    while eng.has_work():
        assert eng.step()
    assert all(s.final()["finish_reason"] == "length" for s in streams)
    launch = eng.stats()["launch"]
    ran = [kind for kind in ("prefill", "decode", "verify")
           if launch[kind]["calls"]]
    assert ran == ["prefill", "decode"] + ["verify"] * (family == "gpt2")
    for kind in ran:
        assert launch[kind]["host_arrays"] == launch[kind]["calls"], kind
    assert handed and all(
        len(host) == 1 and host[0].dtype == np.int32 and host[0].ndim == 1
        for host in handed)
    assert sum(n["host_bytes"] for n in launch.values() if isinstance(
        n, dict)) == sum(host[0].nbytes for host in handed)
    # no request compiled anything
    assert eng.runner.compiled_signatures() \
        == SIGNATURES_AT_THE_PARENT[family]


# ------------------------------------------------- (d) a step still in flight


def _two_steps(runner, between):
    """Two decode steps of the same two lanes, the second fed the ids the
    first left on the device; `between(first)` runs after both launches or
    between them. Returns both steps' results and the packs as the jitted
    calls were handed them, each with a copy taken at the call."""
    handed = []
    inner = runner._decode_jit

    def call(*args):
        handed.append((args[-1], args[-1].copy()))
        return inner(*args)
    call._cache_size = inner._cache_size
    runner._decode_jit = call
    prompt = list(range(5, 18))
    for slot, first, n in ((0, 1, 13), (1, 17, 6)):
        runner.collect(runner.launch_prefill(
            prompt[:n], _pages(runner, first), 0.0, slot=slot))

    def items(pos_a, pos_b, temp):
        return [DecodeItem(-1, pos_a, _pages(runner, 1), temp, slot=0),
                DecodeItem(-1, pos_b, _pages(runner, 17), temp, 7, 0.9,
                           slot=1)]
    first = runner.launch_decode(items(13, 6, 0.7))
    out = between(first)
    second = runner.launch_decode(items(14, 7, 1.3))
    out = out or runner.collect(first)
    return out, runner.collect(second), handed


@pytest.mark.parametrize("family", ["gpt2", "nemotron_h"])
def test_a_later_fill_cannot_change_a_launch_in_flight(family):
    """The engine keeps a step in flight: the next launch is prepared
    and made before this one's results are read, and on the CPU backend
    the runtime may read a host array in place, whenever it runs. Each
    launch's pack is an array of its own, so nothing the second launch
    writes is anything the first reads."""
    ahead = _two_steps(_runner(family), lambda first: None)
    one_by_one_runner = _runner(family)
    one_by_one = _two_steps(one_by_one_runner, one_by_one_runner.collect)
    for (toks, logits), (toks_1, logits_1) in zip(ahead[:2], one_by_one[:2]):
        assert toks == toks_1
        assert np.array_equal(logits, logits_1)
    (a, a_then), (b, b_then) = ahead[2]
    assert a is not b and not np.shares_memory(a, b)
    assert np.array_equal(a, a_then) and np.array_equal(b, b_then)
    assert not np.array_equal(a, b)  # other positions, other temperatures
