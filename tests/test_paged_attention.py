"""The cached-context read's Pallas kernel (ops/paged_attention.py), run
through the interpreter: against the full-width reference
(`context_attention.softmax_over`: every slot of the tables read and
masked) at the serve configurations' head shapes, at the edges of a
lane's length, for a decode step's one row and a verify window's causal
rows, and through `attend_cached` against the tile loops it replaces."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import context_attention as ca
from ray_tpu.ops import paged_attention as pa
from ray_tpu.serve.llm.cache import KVKind, KVLayout

BS, PAGES = 16, 8  # a table of 128 slots

# KV heads, query heads a KV head, head width
HEADS = {"gpt2_large_20x64": (20, 1, 64), "olmoe_16x128": (16, 1, 128),
         "nemotron_gqa_2x16x128": (2, 16, 128)}
# the lanes' lengths in one program
LANES = {
    "edges_of_a_page": [0, 1, 15, 16, 17],
    "mid_page_and_full_table": [40, 128],
    "mixed": [128, 0, 57, 16, 100, 1],
    # five lanes in the 8-row program: the padded rows' tables are null
    "bucket_of_8_for_5_lanes": [90, 33, 70, 12, 5, 0, 0, 0],
}


def _operands(heads, lengths, T, dtype, seed=0, layers=2):
    HK, R, D = heads
    B = len(lengths)
    lay = KVLayout.of(KVKind("full", layers, HK, D, D), 1 + B * PAGES, BS)
    key = jax.random.split(jax.random.PRNGKey(seed), 5)
    rng = np.random.default_rng(seed)
    tables = 1 + rng.permutation(B * PAGES).reshape(B, PAGES)
    tables[np.asarray(lengths) == 0] = 0  # a lane with nothing cached
    # rows past a lane's last valid row read as garbage, never as zeros
    return lay, dict(
        q=jax.random.normal(key[0], (B, T, HK, R, D), dtype),
        k=jax.random.normal(key[1], (B, T, HK, D), dtype),
        v=jax.random.normal(key[2], (B, T, HK, D), dtype),
        k_pages=jax.random.normal(key[3], lay.shape, dtype),
        v_pages=jax.random.normal(key[4], lay.v_shape, dtype),
        tables=jnp.asarray(tables, jnp.int32),
        lengths=jnp.asarray(lengths, jnp.int32))


@pytest.fixture
def two_pages_a_step(monkeypatch):
    """Four steps to a full table: the toy table would fit one step of
    the real size, which would leave the kernel's loop at one turn."""
    monkeypatch.setattr(pa, "STEP_BYTES", 1)
    monkeypatch.setattr(pa, "STEP_SLOTS_MIN", 2 * BS)


def _check(heads, lengths, T, own_valid, dtype, atol):
    lay, ops = _operands(heads, lengths, T, dtype)
    assert pa.pages_a_step(lay, jnp.dtype(dtype).itemsize, PAGES) == 2
    layer = lay.kv_layers - 1  # not the pool's first
    got = pa.paged_attention(**ops, own_valid=own_valid, layout=lay,
                             layer=layer, dtype=dtype, interpret=True)
    want = pa.paged_attention_reference(
        **ops, own_valid=own_valid, layout=lay, layer=layer, dtype=dtype)
    assert got.shape == want.shape == ops["q"].shape
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=atol)


@pytest.mark.parametrize("lanes", LANES)
@pytest.mark.parametrize("heads", HEADS)
def test_decode_row_matches_full_width_softmax(heads, lanes,
                                               two_pages_a_step):
    lengths = LANES[lanes]
    own = jnp.ones((len(lengths), 1, 1), bool)
    _check(HEADS[heads], lengths, 1, own, jnp.float32, 2e-5)


@pytest.mark.parametrize("length", [0, 37, 128])
@pytest.mark.parametrize("heads", HEADS)
def test_verify_window_of_3_rows_is_causal_in_its_own_rows(
        heads, length, two_pages_a_step):
    """T = 3, the third row padding (a draft of one token): row t sees
    its own rows up to t of the two that are real."""
    own = ca.causal_rows(jnp.asarray([[True, True, False]]))
    _check(HEADS[heads], [length], 3, own, jnp.float32, 2e-5)


@pytest.mark.parametrize("heads", HEADS)
def test_bfloat16_pools_within_bf16_rounding(heads, two_pages_a_step):
    lengths = LANES["mixed"]
    own = jnp.ones((len(lengths), 1, 1), bool)
    _check(HEADS[heads], lengths, 1, own, jnp.bfloat16, 3e-2)


def test_pages_a_step_by_the_pool_s_row():
    """A MB or two a step at the serve configurations' rows, whole lane
    tiles of slots, never more than the table."""
    def of(HK, D, layers, table_pages=64):
        lay = KVLayout.of(KVKind("full", layers, HK, D, D), 64, 16)
        return pa.pages_a_step(lay, 2, table_pages)

    assert of(20, 64, 36) == 16  # gpt2-large: 16 x 82 KB
    assert of(16, 128, 8) == 16  # OLMoE: 16 x 131 KB
    assert of(2, 128, 2, table_pages=160) == 128  # nemotron_h: 128 x 16 KB
    assert of(2, 128, 2) == 64  # never more than the table
    assert of(128, 128, 2) == 8  # a page of 1 MB: 128 slots all the same


@pytest.mark.parametrize("T", [1, 3])
def test_attend_cached_hands_few_rows_to_the_kernel(T, read_by_kernel,
                                                    two_pages_a_step):
    """`attend_cached` with the path a TPU takes against the loops the
    CPU keeps, same operands: a decode's row and a verify's rows go to
    the kernel (one `pallas_call` in the program), a chunk's 16 do not."""
    lengths = [57] if T > 1 else [100, 57, 16, 0]
    lay, ops = _operands((4, 2, 32), lengths, T, jnp.float32)
    own = ca.causal_rows(jnp.ones((len(lengths), T), bool))

    def program():  # a new one a path: a trace is kept by its function
        def attend(q, k, v, k_pages, v_pages, tables, lengths):
            ctx = ca.CachedContext.of(lay, k_pages, v_pages, tables, lengths)
            return ca.attend_cached(q, k, v, own, ctx, 1, jnp.float32)
        return attend

    read_by_kernel(False)
    want = jax.jit(program())(**ops)
    assert "pallas_call" not in str(jax.make_jaxpr(program())(**ops))
    read_by_kernel(True)
    assert "pallas_call" in str(jax.make_jaxpr(program())(**ops))
    got = jax.jit(program())(**ops)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    assert not ca.reads_by_kernel(lay, 16)  # a chunk's smallest bucket


def test_the_cpu_keeps_the_loops_and_odd_kinds_never_go():
    """The predicate as it stands here: no TPU, no kernel; and on a TPU
    a window, a selection, a sink, unlike K and V widths, a row or a page
    that is no whole tile all keep the loops."""
    full = KVLayout.of(KVKind("full", 2, 20, 64, 64), 64, 16)
    assert not ca.reads_by_kernel(full, 1)
    import unittest.mock

    with unittest.mock.patch.object(jax, "default_backend", lambda: "tpu"):
        assert ca.reads_by_kernel(full, 1) and ca.reads_by_kernel(full, 5)
        assert not ca.reads_by_kernel(full, 16)
        assert not ca.reads_by_kernel(full, 1, sink=True)
        for kind in (KVKind("window", 2, 20, 64, 64, window=128),
                     KVKind("latent", 2, 1, 640, 128, select=16),
                     KVKind("mimo_full", 2, 4, 192, 128),
                     KVKind("narrow", 2, 2, 16, 16)):
            assert not ca.reads_by_kernel(KVLayout.of(kind, 64, 16), 1), kind
        assert not ca.reads_by_kernel(
            KVLayout.of(KVKind("full", 2, 20, 64, 64), 64, 8), 1)
