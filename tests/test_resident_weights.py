"""The resident parameter tree of a serve runner: each leaf held in the
dtype the family's forwards consume it in, cast once when it is installed
(`gpt2.gpt2_resident_params`, `ModelRunner._install`, `set_params`).

What must hold: the programs give the logits they gave when they cast the
float32 tree inside (round-to-nearest-even of one value is one value, in or
out of a program); the layer-norm leaves, which `_layer_norm` multiplies in
float32, are never rounded; a pushed float32 tree is rounded on arrival and
changes no program's signature; a tree already in the resident dtypes is
taken as it is, the same buffers; a float32-compute configuration sees the
identity."""

import dataclasses
import gc

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import gpt2
from ray_tpu.serve.llm import EngineConfig, LLMEngine, ModelRunner, \
    SamplingParams
from ray_tpu.serve.llm.runner import adapters

BF16 = jnp.dtype(jnp.bfloat16)
F32 = jnp.dtype(jnp.float32)
CAST = 10  # wte, wpe, four kernels and four biases under blocks


def _cfg(dtype=jnp.bfloat16):
    return dataclasses.replace(gpt2.GPT2Config.tiny(), dtype=dtype,
                               remat=False)


def _f32_tree(cfg, seed=0):
    """A float32 tree as a trainer pushes it: GPT-2 init, the zero biases
    and unit layer-norm scales replaced by values bf16 cannot hold."""
    params = gpt2.init_gpt2(jax.random.PRNGKey(seed), cfg)
    leaves, treedef = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(seed + 100), len(leaves))
    return jax.tree.unflatten(treedef, [
        a + 0.01 * jax.random.normal(k, a.shape, a.dtype)
        for a, k in zip(leaves, keys)])


def _is_ln(path):
    return any(getattr(p, "key", "").startswith("ln") for p in path)


def _engine(cfg, params=None):
    return LLMEngine(EngineConfig(
        model="gpt2", model_config=cfg, block_size=4, num_blocks=32,
        max_model_len=32, max_batch_size=2, prefill_chunk_size=8),
        params=params)


@pytest.fixture(scope="module")
def both_sides():
    """The three dense programs of one bf16 runner built on a float32
    tree, run on the resident tree and on the float32 tree itself (the
    cast inside the program, as it was): logits and pools of each."""
    cfg = _cfg()
    given = _f32_tree(cfg)
    r = ModelRunner(adapters()["gpt2"], cfg, given, block_size=4,
                    num_blocks=16, max_model_len=32, max_batch_size=2,
                    prefill_chunk_size=8)
    assert r.weights["cast_leaves"] == CAST
    table = [3, 7, 2, 9]

    def packed(kind, bucket, **fields):
        """The program's one host array (`ModelRunner._pack`) with `fields`
        written in, the rest as a greedy launch that keeps its id nowhere
        leaves them."""
        host, f = r._pack(kind, bucket)
        f["slots" if kind == "decode" else "slot"][...] = -1
        f["topps"][...] = 1.0
        for name, value in fields.items():
            value = np.asarray(value)  # a scalar, or a field's first ids
            f[name][(..., slice(value.shape[-1])) if value.ndim
                    else ...] = value
        return host

    def run(tree):
        out = {}
        ids = r.slot_tokens  # the resident sampled ids
        st = r.state  # {}: gpt2 carries no recurrent state
        _, out["prefill"], k, v, ids, st, _ = jax.jit(r._prefill_impl)(
            tree, r.k_pages, r.v_pages, ids, st, packed(
                "prefill", 8, tokens=np.arange(1, 9), last_idx=7,
                page_ids=table[:2], step=1))
        _, out["chunk"], k, v, ids, st, _ = jax.jit(r._chunk_impl)(
            tree, k, v, ids, st, packed(
                "chunk", 8, tokens=np.arange(9, 17), start=8,
                last_idx=5, page_ids=table[2:4], table=table, step=2))
        _, out["decode"], k, v, ids, st, _ = jax.jit(r._decode_impl)(
            tree, k, v, ids, st, packed(
                "decode", 2, tokens=[5, 1], positions=[14, 0],
                tables=[table, [0] * 4], step=3))
        out["pools"] = np.stack([np.asarray(k, np.float32),
                                 np.asarray(v, np.float32)])
        return {name: np.asarray(a) for name, a in out.items()}

    return run(r.params), run(given)


@pytest.mark.parametrize("program", ["prefill", "chunk", "decode", "pools"])
def test_resident_tree_gives_the_logits_of_the_cast_inside(both_sides,
                                                           program):
    resident, inside = both_sides
    assert np.isfinite(resident[program]).all()
    assert np.abs(resident[program]).max() > 0
    np.testing.assert_array_equal(resident[program], inside[program])


def test_layer_norm_leaves_stay_float32_through_an_update():
    """Kernels, biases and embeddings are rounded once on arrival; scales
    and biases of `ln1`, `ln2`, `lnf` arrive unrounded, at construction and
    through `update_weights`."""
    cfg = _cfg()
    first, second = _f32_tree(cfg, 0), _f32_tree(cfg, 1)
    eng = _engine(cfg, first)
    for given, install in ((first, None), (second, 1)):
        if install:
            eng.update_weights(install, given)
        held = dict(jax.tree_util.tree_leaves_with_path(eng.runner.params))
        n_ln = 0
        for path, want in jax.tree_util.tree_leaves_with_path(given):
            got = held[path]
            if _is_ln(path):
                n_ln += 1
                assert got.dtype == F32
                np.testing.assert_array_equal(np.asarray(got),
                                              np.asarray(want))
                # the test's values are ones a rounding would change
                assert not np.array_equal(
                    np.asarray(want),
                    np.asarray(want.astype(BF16).astype(F32)))
            else:
                assert got.dtype == BF16
                np.testing.assert_array_equal(
                    np.asarray(got.astype(F32)),
                    np.asarray(want.astype(BF16).astype(F32)))
        assert n_ln == 6 and len(held) == n_ln + CAST


def test_float32_update_keeps_every_program_and_counts_its_casts():
    from ray_tpu.util.metrics import prometheus_text

    cfg = _cfg()

    def f32_embeddings():
        gc.collect()
        return [a for a in jax.live_arrays() if a.dtype == F32
                and a.shape == (cfg.padded_vocab, cfg.n_embd)]

    others = f32_embeddings()  # other tests' of this process, if any
    eng = _engine(cfg)  # the engine's own init: nothing handed in
    # the float32 tree the engine created is gone with its constructor
    assert not [a for a in f32_embeddings()
                if not any(a is b for b in others)]
    resident = sum(a.nbytes for a in jax.tree.leaves(eng.runner.params))
    n_f32 = gpt2.count_params(eng.runner.params) * 4
    assert n_f32 / 2 < resident < n_f32 / 2 * 1.01
    assert eng.stats()["weights"] == {
        "resident_bytes": resident, "cast_leaves": CAST, "installs": 1}
    sampling = SamplingParams(max_tokens=4)
    before = eng.generate(list(range(1, 12)), sampling, drive=True)
    programs = eng.runner.compiled_signatures()
    eng.update_weights(1, _f32_tree(cfg, 2))
    after = eng.generate(list(range(1, 12)), sampling, drive=True)
    assert eng.runner.compiled_signatures() == programs
    assert after["token_ids"] != before["token_ids"]  # other weights
    assert eng.stats()["weights"] == {
        "resident_bytes": resident, "cast_leaves": CAST, "installs": 2}
    # what the engine holds, pushed back: nothing left to cast
    eng.update_weights(2, eng.runner.params)
    assert eng.stats()["weights"]["cast_leaves"] == 0
    lines = prometheus_text().splitlines()
    for name, want in (("serve_llm_weight_bytes", resident),
                       ("serve_llm_weight_cast_leaves", 0)):
        assert [float(ln.split()[-1]) for ln in lines
                if ln.startswith(name + "{") and 'model="gpt2"' in ln] \
            == [want]


@pytest.mark.parametrize("family", ["gpt2", "llama"])
def test_tree_in_resident_dtypes_is_installed_without_a_copy(family):
    adapter = adapters()[family]
    if family == "gpt2":
        cfg = _cfg()
        tree = gpt2.gpt2_resident_params(_f32_tree(cfg), cfg)
    else:
        cfg = dataclasses.replace(adapter.presets["tiny"](),
                                  dtype=jnp.bfloat16,
                                  param_dtype=jnp.bfloat16)
        tree = adapter.init_fn(jax.random.PRNGKey(0), cfg)
    assert BF16 in {a.dtype for a in jax.tree.leaves(tree)}
    r = ModelRunner(adapter, cfg, tree, block_size=4, num_blocks=8,
                    max_model_len=16, max_batch_size=1)

    def same_buffers():
        return all(a is b for a, b in zip(jax.tree.leaves(r.params),
                                          jax.tree.leaves(tree)))

    assert same_buffers() and r.weights["cast_leaves"] == 0
    r.set_params(tree)
    assert same_buffers()
    assert r.weights["cast_leaves"] == 0 and r.weights["installs"] == 2


def test_float32_compute_is_the_identity():
    cfg = _cfg(jnp.float32)
    tree = _f32_tree(cfg)
    held = gpt2.gpt2_resident_params(tree, cfg)
    assert all(a is b for a, b in zip(jax.tree.leaves(held),
                                      jax.tree.leaves(tree)))
    eng = _engine(cfg, tree)
    assert all(a is b for a, b in zip(jax.tree.leaves(eng.runner.params),
                                      jax.tree.leaves(tree)))
    assert eng.stats()["weights"] == {
        "resident_bytes": gpt2.count_params(tree) * 4, "cast_leaves": 0,
        "installs": 1}
