"""The cached-context read's Pallas kernel (ops/paged_attention.py), run
through the interpreter: against the full-width reference
(`context_attention.softmax_over`: every slot of the tables read and
masked) at the serve configurations' head shapes, at the edges of a
lane's length, for a decode step's one row and a verify window's causal
rows, and through `attend_cached` against the tile loops it replaces. A
latent kind read whole (one pool: 32 heads on a 640-lane row whose first
512 lanes are the values, under a scale of its own) is one more of the
head shapes, through `attend_latent` against its loops; the kernel of the
kinds it took before is held to the text it lowered to."""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import context_attention as ca
from ray_tpu.ops import paged_attention as pa
from ray_tpu.serve.llm.cache import KVKind, KVLayout

BS, PAGES = 16, 8  # a table of 128 slots

# KV heads, query heads a KV head, head width[, the lanes of a row that are
# its values: a kind with one pool, scores under `LATENT_SCALE`]
HEADS = {"gpt2_large_20x64": (20, 1, 64), "olmoe_16x128": (16, 1, 128),
         "nemotron_gqa_2x16x128": (2, 16, 128),
         "xing4_latent_32_on_640": (1, 32, 640, 512)}
LATENT_SCALE = 0.1147  # xing4's mscale^2 / sqrt(192), not 1 / sqrt(640)
# the lanes' lengths in one program
LANES = {
    "edges_of_a_page": [0, 1, 15, 16, 17],
    "mid_page_and_full_table": [40, 128],
    "mixed": [128, 0, 57, 16, 100, 1],
    # five lanes in the 8-row program: the padded rows' tables are null
    "bucket_of_8_for_5_lanes": [90, 33, 70, 12, 5, 0, 0, 0],
}


def _operands(heads, lengths, T, dtype, seed=0, layers=2):
    """(layout, operands, scale): a kind with one pool brings rows of no
    lanes for the pool it has not, as its family's forwards do, and its
    own values are its own rows' first lanes."""
    HK, R, D, *values = heads
    B = len(lengths)
    lay = KVLayout.of(KVKind("latent" if values else "full", layers, HK, D,
                             0 if values else D), 2 + B * PAGES, BS)
    key = jax.random.split(jax.random.PRNGKey(seed), 5)
    rng = np.random.default_rng(seed)
    tables = 1 + rng.permutation(B * PAGES).reshape(B, PAGES)
    tables[np.asarray(lengths) == 0] = 0  # a lane with nothing cached
    # the entry behind a lane's last page is a decoy: a page of rows that
    # would swamp the softmax, which nothing may read
    decoy = 1 + B * PAGES
    for b, n in enumerate(lengths):
        if 0 < n <= (PAGES - 1) * BS:
            tables[b, -(-n // BS)] = decoy
    k = jax.random.normal(key[1], (B, T, HK, D), dtype)
    # rows past a lane's last valid row read as garbage, never as zeros
    ops = dict(
        q=jax.random.normal(key[0], (B, T, HK, R, D), dtype),
        k=k, v=k[..., :values[0]] if values
        else jax.random.normal(key[2], (B, T, HK, D), dtype),
        k_pages=jax.random.normal(key[3], lay.shape, dtype)
        .at[:, decoy].set(64.0),
        v_pages=jax.random.normal(key[4], lay.v_shape, dtype),
        tables=jnp.asarray(tables, jnp.int32),
        lengths=jnp.asarray(lengths, jnp.int32))
    return lay, ops, LATENT_SCALE if values else None


@pytest.fixture
def two_pages_a_step(monkeypatch):
    """Four steps to a full table: the toy table would fit one step of
    the real size, which would leave the kernel's loop at one turn."""
    monkeypatch.setattr(pa, "STEP_BYTES", 1)
    monkeypatch.setattr(pa, "STEP_SLOTS_MIN", 2 * BS)
    # and a full step's two copies are one turn of the unrolled loop
    monkeypatch.setattr(pa, "STARTS_A_TURN", 2)


def _check(heads, lengths, T, own_valid, dtype, atol):
    lay, ops, scale = _operands(heads, lengths, T, dtype)
    assert pa.pages_a_step(lay, jnp.dtype(dtype).itemsize, PAGES) == 2
    layer = lay.kv_layers - 1  # not the pool's first
    got = pa.paged_attention(**ops, own_valid=own_valid, layout=lay,
                             layer=layer, dtype=dtype, scale=scale,
                             interpret=True)
    # the oracle in float32 on the same (rounded) operands: its own bf16
    # scores would be the coarser of the two
    exact = {name: a.astype(jnp.float32) if a.dtype == dtype else a
             for name, a in ops.items()}
    want = pa.paged_attention_reference(
        **exact, own_valid=own_valid, layout=lay, layer=layer,
        dtype=jnp.float32, scale=scale)
    assert got.shape == want.shape \
        == ops["q"].shape[:-1] + ops["v"].shape[-1:]
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
    # a kind with one pool is reckoned on its one row: 64 pages of 20 KB
    # (1,024 slots) at xing4's 640 lanes, where K and V of 640 would be 32
    latent = KVLayout.of(KVKind("latent", 6, 1, 640, 0), 4096, 16)
    assert pa.pages_a_step(latent, 2, 2112) == 64
    assert of(1, 640, 6, table_pages=2112) == 32


@pytest.mark.parametrize("T", [1, 3])
@pytest.mark.parametrize("kind", ["full", "latent"])
def test_few_rows_a_lane_go_to_the_kernel(kind, T, read_by_kernel,
                                          two_pages_a_step):
    """`attend_cached` (a full kind) and `attend_latent` (a latent kind
    read whole: 4 heads on a row of 40 lanes whose first 24 are the
    values) with the path a TPU takes against the loops the CPU keeps,
    same operands: a decode's row and a verify's rows go to the kernel
    (one `pallas_call` in the program), a chunk's 16 do not."""
    lengths = [57] if T > 1 else [100, 57, 16, 0]
    heads = (4, 2, 32) if kind == "full" else (1, 4, 40, 24)
    lay, ops, scale = _operands(heads, lengths, T, jnp.float32)
    own = ca.causal_rows(jnp.ones((len(lengths), T), bool))

    def program():  # a new one a path: a trace is kept by its function
        def attend(q, k, v, k_pages, v_pages, tables, lengths):
            ctx = ca.CachedContext.of(lay, k_pages, v_pages, tables, lengths)
            if kind == "full":
                return ca.attend_cached(q, k, v, own, ctx, 1, jnp.float32)
            return ca.attend_latent(q[:, :, 0], k[:, :, 0], own, ctx, 1,
                                    jnp.float32, values=24, scale=scale)
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
    that is no whole tile all keep the loops; a latent kind read whole
    (xing4's: no V row) goes like a full kind, one under an indexer
    (glm-5's) does not."""
    full = KVLayout.of(KVKind("full", 2, 20, 64, 64), 64, 16)
    latent = KVLayout.of(KVKind("latent", 6, 1, 640, 0), 64, 16)
    assert not ca.reads_by_kernel(full, 1)
    assert not ca.reads_by_kernel(latent, 1)
    import unittest.mock

    with unittest.mock.patch.object(jax, "default_backend", lambda: "tpu"):
        for lay in (full, latent):
            assert ca.reads_by_kernel(lay, 1) and ca.reads_by_kernel(lay, 5)
            assert not ca.reads_by_kernel(lay, 16)
            assert not ca.reads_by_kernel(lay, 1, sink=True)
        for kind in (KVKind("window", 2, 20, 64, 64, window=128),
                     KVKind("latent", 2, 1, 640, 128, select=16),
                     KVKind("mimo_full", 2, 4, 192, 128),
                     KVKind("narrow", 2, 2, 16, 16)):
            assert not ca.reads_by_kernel(KVLayout.of(kind, 64, 16), 1), kind
        assert not ca.reads_by_kernel(
            KVLayout.of(KVKind("full", 2, 20, 64, 64), 64, 8), 1)
        # a latent row of 4.5 lane tiles (576: GLM-5's before its padding)
        assert not ca.reads_by_kernel(
            KVLayout.of(KVKind("latent", 6, 1, 576, 0), 64, 16), 1)


# sha256 of the jaxpr (the `pallas_call`, its kernel's body and what feeds
# it) of the kinds the kernel took before it took a kind with one pool,
# at the serve cells' shapes, recorded at the parent commit (6b5f617) by
# `_kernel_text` itself: KV heads, query heads a KV head, head width,
# layers, lanes, rows a lane, pages a table
AT_THE_PARENT = {
    **{f"gpt2-large.decode-{n}": ((20, 1, 64, 36, n, 1, 64), h)
       for n, h in (
           (1, "059ffcd51f54858b65deea678a8d875dc22254035a4732ab6b208fb2bdf99a26"),
           (2, "19c54e497e729c7f7ea68ae3e65f22b14e1c85ed724320830c88daf0257ac447"),
           (4, "78a10f96e064535c454b64a02ef422d09b39de100796da56ad0f1bd96510443d"),
           (8, "5372acbb3eb5ea7b5f1a8bc783e892cda1be8c9f7a75aab9b26637a5847b49b7"),
           (16, "a0414daee3bdbb281736e7fe0521232a1b60a664a72911d23410bc098d075c16"),
           (32, "e5d3c1c63eabb208dd1456905025c77518e86a17723bd81dfb304f5e9202a799"))},
    "gpt2-large.verify-5": (
        (20, 1, 64, 36, 1, 5, 64),
        "808fc3ef8fcd6951a843eb52a4947a0980ae0f5a286110a47eb1fa763e38a260"),
    "olmoe-1b-7b.decode-16": (
        (16, 1, 128, 8, 16, 1, 64),
        "f23702eb60d415fdb5af6393a81bb7dd6e8075061246ba83bdf4ff33ea53f9e0"),
    "nemotron-3-nano-30b-a3b.decode-32": (
        (2, 16, 128, 2, 32, 1, 160),
        "4dea55ec1f8dbf4094b754cb08dcc62c54bb9231baaecac1c6a379a8efbbb0de"),
    "lfm2-8b-a1b.decode-64": (
        (8, 4, 64, 6, 64, 1, 536),
        "9096a04f2bf267f606d140ee83f9a40033f6018853bfe4464114fd8965f0e1b7"),
    "granite-4.0-h-small.decode-64": (
        (8, 4, 128, 1, 64, 1, 112),
        "1a588144743439a22832dc1561a40e24fcac2015b028592d4a2f742b9f534d34"),
}


def _kernel_text(HK, R, D, layers, B, T, pages, values=None):
    """`values`: a kind with one pool, whose row's first `values` lanes
    are its values."""
    lay = KVLayout.of(KVKind("kind", layers, HK, D, 0 if values else None),
                      4096, 16)
    S, bf = jax.ShapeDtypeStruct, jnp.bfloat16
    Dv = values or D

    def read(q, k, v, own, kp, vp, tables, lengths, layer):
        return pa.paged_attention(q, k, v, own, kp, vp, tables, lengths,
                                  layout=lay, layer=layer, dtype=bf)

    return str(jax.make_jaxpr(read)(
        S((B, T, HK, R, D), bf), S((B, T, HK, D), bf), S((B, T, HK, Dv), bf),
        S((B, T, T), jnp.bool_), S(lay.shape, bf), S(lay.v_shape, bf),
        S((B, pages), jnp.int32), S((B,), jnp.int32), S((), jnp.int32)))


@pytest.mark.parametrize("case", sorted(AT_THE_PARENT))
def test_the_other_kinds_kernel_lowers_as_before(case):
    """One algorithm, its parameters picked from the layout: for a kind
    with K and V pools the kernel and what feeds it trace to the text
    they traced to before the kernel took a kind with one pool."""
    shapes, want = AT_THE_PARENT[case]
    assert hashlib.sha256(_kernel_text(*shapes).encode()).hexdigest() == want


def test_a_kind_with_one_pool_copies_one_page_and_picks_no_blocks():
    """xing4's decode-32 against gpt2-large's: one async copy a page
    where K and V take two, started `STARTS_A_TURN` a turn of the loop
    and one a turn for what is left (at both places that start a block),
    waited for at once where a block's 64 pages are all there and in the
    loop for a lane's last; one double buffer of 1,024 slots x 640 lanes
    and one semaphore a buffer; and the accumulator (32, 512) is the
    output: none of the masked sums that pick a head's block."""
    latent = _kernel_text(1, 32, 640, 6, 32, 1, 2112, values=512)
    full = _kernel_text(20, 1, 64, 36, 32, 1, 64)
    assert latent.count("dma_start") == 2 * (pa.STARTS_A_TURN + 1)
    assert latent.count("dma_wait") == 1 + 1
    assert (full.count("dma_start"), full.count("dma_wait")) == (4, 2)
    assert "bf16[2,1024,640]" in latent and "bf16[2,1024,512]" not in latent
    assert "dma_sem[1,2]" in latent and "dma_sem[2,2]" in full
    assert "reduce_sum[axes=(0,)" in full
    assert "reduce_sum[axes=(0,)" not in latent
