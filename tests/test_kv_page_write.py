"""A prompt's and a chunk's rows stored a page at a time (ISSUE 37): end to
end, the engine as it is against the same engine held to the write of
before (`_rowwise`: one scatter index a row, every padded row to the null
page), and each reason why padding behind a sequence's frontier, in the
sequence's own last page, is safe (`serve/llm/cache.py`'s module
docstring) held by a test of its own.

On the CPU the two engines must agree on every token and log-prob of every
request: what differs between them is where padded rows land, and no read
may see one."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.serve.llm import SamplingParams, SpeculativeConfig
from ray_tpu.serve.llm.runner import DecodeItem, ModelRunner, adapters
# engines at tiny presets (pages of 4, chunks of 8: a chunk is two whole
# pages), seeded prompts, and what each client saw of its request
from tests.test_step_overlap import _engine, _prompts, _serve

BS = 4


def _rowwise(patch):
    """The write of before PR 37, for engines BUILT AND RUN while `patch`
    holds: a block id a ROW (the null page for a padded row), scattered
    row by row. The program makes the ids itself, from the page ids and
    the count of valid rows in its pack (since PR 56 a launch's one host
    array has a page id a group of rows and no field for one a row)."""
    unpacked = ModelRunner._unpacked

    def noting_the_valid_rows(self, kind, host):
        fields = unpacked(self, kind, host)
        self._traced_last_idx = fields.get("last_idx")
        return fields

    def write_rows(self, k_pages, v_pages, page_ids, k, v):
        at = jnp.arange(jax.tree.leaves(k)[0].shape[2])  # the bucket's rows
        ids = tuple(jnp.where(at <= self._traced_last_idx,
                              pages[at // self.block_size], 0)
                    for pages in page_ids)
        return self._write(k_pages, v_pages, ids, at % self.block_size,
                           k, v, lane=0)

    patch.setattr(ModelRunner, "_unpacked", noting_the_valid_rows)
    patch.setattr(ModelRunner, "_write_pages", write_rows)


def _both(monkeypatch, run, **kw):
    """`run(engine)` on the engine as it is and on the row-wise one."""
    got = run(_engine(**kw))
    with monkeypatch.context() as patch:
        _rowwise(patch)
        want = run(_engine(**kw))
    return got, want


def _greedy(max_tokens):
    return SamplingParams(max_tokens=max_tokens, logprobs=True)


# ------------------------------------------- engine against engine, greedy


def test_prompt_ending_mid_page_then_decoding_across_the_pages_end(
        monkeypatch):
    """A prompt of 6 leaves its second page half padding; 2 x block_size
    decode steps then write over the padding, cross the page's end and
    open two more pages."""
    reqs = [(p, _greedy(2 * BS + 1)) for p in _prompts([6, 13, 3])]
    got, want = _both(monkeypatch, lambda e: _serve(e, reqs))
    assert got == want
    assert all(o["finish_reason"] == "length" for o in got)


def test_a_shared_full_page_prefix_is_read_and_never_written(monkeypatch):
    """The second request takes the first's three full pages and its chunk
    starts behind them: the same hit and the same tokens as row-wise, and
    the shared pages hold afterwards, to the bit, what they held before."""
    first = _prompts([14])[0]
    second = first[:13] + [7, 8, 9, 5, 2]  # 18 tokens, 12 of them cached
    seen = {}

    def run(engine):
        out = _serve(engine, [(first, _greedy(3))])
        shared = sorted(engine.pool._hash_of)  # the registered pages
        before = [np.asarray(p)[:, shared] for p in
                  engine.runner.k_pages + engine.runner.v_pages]
        out += _serve(engine, [(second, _greedy(6))])
        after = [np.asarray(p)[:, shared] for p in
                 engine.runner.k_pages + engine.runner.v_pages]
        seen[len(seen)] = (shared, before, after)
        return out

    got, want = _both(monkeypatch, run)
    assert got == want
    assert [o["cached_tokens"] for o in got] == [0, 12]
    shared, before, after = seen[0]  # the engine as it is
    assert len(shared) >= 3
    for b, a in zip(before, after):
        assert np.abs(b).max() > 0
        np.testing.assert_array_equal(a, b)


def test_a_preempted_sequence_recomputes_to_the_same_tokens(monkeypatch):
    """A pool too small for its three lanes: a victim is preempted and
    its prompt and tokens so far go through the chunk program again, into
    other pages, some of them with another sequence's rows still in them."""
    kw = dict(num_blocks=14, max_model_len=32, enable_prefix_cache=False)
    reqs = [(p, _greedy(14)) for p in _prompts([9, 10, 8])]
    got, want = _both(monkeypatch, lambda e: _serve(e, reqs), **kw)
    assert got == want
    assert sum(o["preemptions"] for o in got) > 0


def test_a_first_verify_from_inside_the_half_padded_page(monkeypatch):
    """Speculation, 4 drafts: the prompts repeat themselves, so the
    proposer drafts at once, and the first verify dispatch starts at the
    frontier inside the page whose tail the prefill left as padding: its
    rows overwrite the padding, its context stops before it."""
    prompts = [[5, 6, 7, 5, 6, 7, 5, 6, 7, 5], [9, 9, 9, 9, 9, 9], [3, 4] * 7]
    assert all(len(p) % BS for p in prompts)
    reqs = [(p, _greedy(10)) for p in prompts]
    spec = dict(speculative=SpeculativeConfig(num_draft_tokens=4))
    proposed = []

    def run(engine):
        out = _serve(engine, reqs)
        proposed.append(engine.stats()["spec_proposed"])
        return out

    got, want = _both(monkeypatch, run, **spec)
    assert got == want
    assert proposed[0] > 0
    plain = _serve(_engine(), reqs)
    assert [o["tokens"] for o in got] == [o["tokens"] for o in plain]


def test_two_kinds_of_kv_layer_with_pages_released_mid_prompt(monkeypatch):
    """mimo_v2 tiny: a prompt of four chunks against a window of 8, so the
    window kind's pages go back to their pool, and to the other lanes,
    while the prompt is still being prefilled; every chunk's rows fall in
    pages the sequence holds at that moment, in both kinds."""
    reqs = [(p, _greedy(9)) for p in _prompts([30, 7, 21, 10])]
    launches = []
    page_ids = ModelRunner._page_ids

    def spy(self, table, start, n, width, out):
        page_ids(self, table, start, n, width, out)
        launches.append((n, out.copy()))

    monkeypatch.setattr(ModelRunner, "_page_ids", spy)
    engine = _engine("mimo_v2")
    got = _serve(engine, reqs)
    kv = engine.stats()["kv"]
    assert kv["window"]["released_behind_window"] > 0
    assert kv["window"]["pages_used"] == kv["full"]["pages_used"] == 0
    # a chunk owns all of its rows' pages while it runs: no valid group of
    # any launch was pointed at the null page, every other group was
    assert len(launches) >= 4 + 1 + 3 + 2
    for n, ids in launches:
        assert len(ids) == 2
        for kind in ids:
            valid = -(-n // BS)
            assert (kind[:valid] > 0).all() and not kind[valid:].any()
            assert len(set(kind[:valid])) == valid
    with monkeypatch.context() as patch:
        _rowwise(patch)
        want = _serve(_engine("mimo_v2"), reqs)
    assert got == want


def test_recurrent_state_carried_chunk_to_chunk_beside_the_write(
        monkeypatch):
    """nemotron_h tiny: K and V in two of its layers, Mamba-2 state in the
    others, a prompt of three chunks whose last is mostly padding."""
    reqs = [(p, _greedy(7)) for p in _prompts([17, 6, 26])]
    got, want = _both(monkeypatch, lambda e: _serve(e, reqs),
                      model="nemotron_h")
    assert got == want
    assert all(len(o["tokens"]) == 7 for o in got)


# ------------------------------------------------------------ the counter


@pytest.mark.parametrize("model", ["gpt2", "mimo_v2"])
def test_rows_written_add_up_to_what_the_requests_wrote(model):
    """By kind of KV layer: the prompts' rows a page at a time, one row a
    decode step and lane (a request's last token is sampled, never fed),
    warm-up left out; and the same on the metrics page."""
    from ray_tpu.util.metrics import prometheus_text
    from ray_tpu.util.watchtower import parse_prometheus

    sizes = [(6, 5), (19, 9), (11, 1), (8, 4)]
    engine = _engine(model)
    engine.warmup()
    for kind in engine.stats()["kv"].values():
        assert kind["rows_written_paged"] == kind["rows_written_rowwise"] == 0
    out = _serve(engine, [(p, _greedy(m)) for p, (_, m) in
                          zip(_prompts([n for n, _ in sizes]), sizes)])
    assert all(o["finish_reason"] == "length" for o in out)
    kv = engine.stats()["kv"]
    assert sorted(kv) == (["full", "window"] if model == "mimo_v2"
                          else ["full"])
    for kind in kv.values():
        assert kind["rows_written_paged"] == sum(n for n, _ in sizes)
        assert kind["rows_written_rowwise"] == sum(m - 1 for _, m in sizes)
    series = {(dict(tags)["kind"], dict(tags)["path"]): value
              for (name, tags), value in
              parse_prometheus(prometheus_text()).items()
              if name == "serve_llm_kv_rows_written_total"
              and dict(tags).get("model") == engine.config.model}
    for name, kind in kv.items():
        assert series[(name, "paged")] >= kind["rows_written_paged"]
        assert series[(name, "rowwise")] >= kind["rows_written_rowwise"]


def test_a_bucket_under_a_page_is_counted_and_written_row_by_row(
        monkeypatch):
    """`prefill_bucket_min` under `block_size`: a prompt of 2 runs in a
    bucket of 2 rows, half a page, which takes the row-wise path (and is
    counted so); longer prompts' buckets are whole pages. The same tokens
    either way."""
    reqs = [(p, _greedy(4)) for p in _prompts([2, 7, 1])]
    kw = dict(prefill_bucket_min=2)
    engines = []

    def run(engine):
        engines.append(engine)
        return _serve(engine, reqs)

    got, want = _both(monkeypatch, run, **kw)
    assert got == want
    kv = engines[0].stats()["kv"]["full"]
    assert kv["rows_written_paged"] == 7
    assert kv["rows_written_rowwise"] == 2 + 1 + 3 * 3


# ------------------------------------- why padding behind the frontier is safe


def _runner(num_draft_tokens=0):
    adapter = adapters()["gpt2"]
    cfg = dataclasses.replace(adapter.presets["tiny"](), dtype=jnp.float32,
                              remat=False)
    params = adapter.init_fn(jax.random.PRNGKey(0), cfg)
    return ModelRunner(adapter, cfg, params, block_size=BS, num_blocks=16,
                       max_model_len=32, max_batch_size=2,
                       prefill_chunk_size=8,
                       num_draft_tokens=num_draft_tokens)


def _poison(runner, page, first_slot, value=1e4):
    """Overwrite slots `first_slot..` of `page`, every layer, K and V."""
    runner.k_pages = tuple(p.at[:, page, first_slot:].set(value)
                           for p in runner.k_pages)
    runner.v_pages = tuple(p.at[:, page, first_slot:].set(value)
                           for p in runner.v_pages)


@pytest.mark.parametrize("poisoned", ["prefill", "chunk"])
def test_no_read_sees_a_slot_behind_the_frontier(poisoned):
    """Whatever the slots behind the frontier hold (here 1e4 in place of
    the padded rows), a chunk, a decode step and every later one give the
    logits they give over the padded rows: each context read stops at the
    lane's length, and decode writes position `pos` before any program
    reads it."""
    table = [3, 7, 2, 9, 5, 11]
    prompt, tail = _prompts([6])[0], _prompts([5], seed=1)[0]

    def run(poison):
        r = _runner()
        out = [r.prefill(prompt, table, 0.0)]  # rows 0..5, page 7 half full
        if poison == "prefill":
            _poison(r, 7, 2)
        pos = len(prompt)
        if poisoned == "chunk":  # a chunk from the next page's edge
            pos = 8
            r2 = r.prefill_chunk(prompt[:2] + tail, 8, table, 0.0)
            out.append(r2)  # rows 8..14, page 9 three quarters full
            if poison == "chunk":
                _poison(r, 9, 3)
            pos += 2 + len(tail)
        tok = out[-1][0]
        for step in range(2 * BS):
            toks, logits = r.decode([DecodeItem(tok, pos + step, table, 0.0)])
            out.append((toks[0], logits[0]))
            tok = toks[0]
        return out

    want, got = run(None), run(poisoned)
    assert [t for t, _ in got] == [t for t, _ in want]
    for (_, a), (_, b) in zip(got, want):
        np.testing.assert_array_equal(a, b)


def test_verify_from_the_frontier_overwrites_the_padding():
    """A verify dispatch that starts inside the half-padded page: the same
    committed tokens and logits whatever the padding held, and afterwards
    the page holds the dispatch's rows where the padding was."""
    table = [3, 7, 2, 9]
    prompt = _prompts([6])[0]

    def run(poison):
        r = _runner(num_draft_tokens=4)
        tok, _ = r.prefill(prompt, table, 0.0)
        if poison:
            _poison(r, 7, 2)
        toks, logits = r.verify(tok, 6, [1, 2, 3], table, 0.0)
        return toks, logits, np.asarray(r.k_pages[0])[:, 7]

    want, got = run(False), run(True)
    assert got[0] == want[0]
    np.testing.assert_array_equal(got[1], want[1])
    # rows 6 and 7 of the sequence (the page's last two slots) are the
    # dispatch's own in both runs: nothing of the 1e4 is left there
    np.testing.assert_array_equal(got[2], want[2])
    assert np.abs(got[2]).max() < 1e3


def test_only_a_page_of_real_rows_is_ever_registered():
    """A prompt of 6 fills one page and half of the next: the prefix index
    takes the full one alone, and the half-padded page goes back to the
    free list unregistered when the request ends; decode steps that fill
    it with real rows make it registrable."""
    prompt = _prompts([6])[0]
    engine = _engine()
    _serve(engine, [(prompt, _greedy(1))])
    assert engine.pool.stats()["registered"] == 6 // BS
    assert engine.pool.stats()["cached"] == 6 // BS
    engine = _engine()
    first = _serve(engine, [(prompt, _greedy(4))])  # rows 0..8 written
    assert engine.pool.stats()["registered"] == 2
    # and shared as it should be: a request that goes on as the first did
    # takes both pages
    twin = prompt + first[0]["tokens"][:3] + [5]
    out = _serve(engine, [(twin, _greedy(2))])
    assert out[0]["cached_tokens"] == 2 * BS
