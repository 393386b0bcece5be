"""Pushed streams (ISSUE 46): a streaming method may return a stream source
(`core/stream_push.py`) in place of a generator; what a producer's loop puts
on its writers leaves at its flush, a `stream_items` message an owner, and
the request's thread sleeps from the stream's start to its end. The LLM
deployment returns one over every request, so a decode step's tokens of all
lanes leave the replica in one message."""

import sys
import threading
import time

import cloudpickle
import numpy as np
import pytest

import ray_tpu
from ray_tpu.cluster_utils import Cluster
from ray_tpu.core.specs import INLINE_THRESHOLD

cloudpickle.register_pickle_by_value(sys.modules[__name__])


@pytest.fixture(scope="module")
def cluster():
    c = Cluster(initialize_head=True, head_node_args={"num_cpus": 4})
    c.add_node(num_cpus=4)
    c.wait_for_nodes()
    ray_tpu.init(address=c.address)
    yield c
    from ray_tpu import serve

    serve.shutdown()
    ray_tpu.shutdown()
    c.shutdown()


class Account:
    """What a source names as its items' observer: counts what the
    shipper tells it, as the engine's `StreamAccount` does."""

    def __init__(self):
        self.pushed = 0
        self.messages = 0

    def shipping(self, put_times):
        account = self

        class Message:
            def __enter__(self):
                pass

            def __exit__(self, *exc):
                account.pushed += len(put_times)
                account.messages += 1

        return Message()


class Source:
    """`values` pushed at once; iterable too, as every source is."""

    def __init__(self, values, account=None, log=None):
        self.values = values
        self.account = account
        self.log = log if log is not None else []

    def stream_to(self, writer):
        self.log.append("pushed")
        for v in self.values:
            writer.put(v, self.account)
        writer.close()
        writer.wait()

    def __iter__(self):
        self.log.append("pulled")
        for v in self.values:
            self.log.append(("yield", v))
            yield v


class Lane:
    """A source as the engine's are: it leaves its writer with the
    producer's loop and sleeps until the stream is over."""

    def __init__(self, producer):
        self.producer = producer

    def stream_to(self, writer):
        self.producer.lanes.append(writer)
        if not writer.wait():
            self.producer.log.append("let go")


@ray_tpu.remote
class Producer:
    """Hosts sources; `step` is its loop's turn: one item a lane, then
    one flush."""

    def __init__(self):
        self.account = Account()
        self.log = []
        self.lanes = []

    def numbers(self, n):
        return Source([{"i": i} for i in range(n)], self.account, self.log)

    def blocks(self, n):
        return Source([np.full((1 << 16,), i, dtype=np.float32)
                       for i in range(n)], self.account)

    def lane(self):
        return Lane(self)

    def waiting(self):
        return len(self.lanes)

    def step(self, value, last=False):
        for writer in self.lanes:
            writer.put(value, self.account)
        self.lanes[0].flush()
        if last:
            for writer in self.lanes:
                writer.close()

    def counts(self):
        return {"pushed": self.account.pushed,
                "messages": self.account.messages}

    def read_log(self):
        return list(self.log)


def _producer():
    # streams hold a thread each; the probes and `step` need their own
    return Producer.options(max_concurrency=8).remote()


def _wait_for(probe, timeout=30.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if probe():
            return
        time.sleep(0.02)
    raise AssertionError("timed out")


# ---------------------------------------------------------------------------
# the protocol, on a source driven by hand
# ---------------------------------------------------------------------------

def test_items_arrive_in_order_under_the_ids_a_pulled_stream_gives(cluster):
    from ray_tpu.core.worker_main import WorkerRuntime

    p = _producer()
    g = p.numbers.options(num_returns="streaming").remote(9)
    refs = list(g)
    assert [ray_tpu.get(r) for r in refs] == [{"i": i} for i in range(9)]
    # `stream_end`'s count is the items: the iterator stopped at nine
    task_id = g._task_id
    assert [r.id.binary() for r in refs] == [
        WorkerRuntime.stream_item_oid(task_id, i) for i in range(9)]
    assert ray_tpu.get(p.read_log.remote()) == ["pushed"]
    # nothing left before the close's flush: nine items, one message
    assert ray_tpu.get(p.counts.remote()) == {"pushed": 9, "messages": 1}


def test_two_sources_of_one_step_leave_in_one_message_an_owner(cluster):
    p = _producer()
    a = p.lane.options(num_returns="streaming").remote()
    b = p.lane.options(num_returns="streaming").remote()
    _wait_for(lambda: ray_tpu.get(p.waiting.remote()) == 2)
    ray_tpu.get(p.step.remote("x"))
    assert ray_tpu.get(next(a)) == "x" and ray_tpu.get(next(b)) == "x"
    assert ray_tpu.get(p.counts.remote()) == {"pushed": 2, "messages": 1}
    ray_tpu.get(p.step.remote("y", last=True))
    assert [ray_tpu.get(r) for r in a] == ["y"]
    assert [ray_tpu.get(r) for r in b] == ["y"]
    assert ray_tpu.get(p.counts.remote()) == {"pushed": 4, "messages": 2}


def test_two_owners_get_a_message_each(cluster):
    p = _producer()

    @ray_tpu.remote
    def consume(producer):
        g = producer.lane.options(num_returns="streaming").remote()
        return [ray_tpu.get(r) for r in g]

    mine = p.lane.options(num_returns="streaming").remote()
    theirs = consume.remote(p)
    _wait_for(lambda: ray_tpu.get(p.waiting.remote()) == 2)
    ray_tpu.get(p.step.remote("x", last=True))
    assert [ray_tpu.get(r) for r in mine] == ["x"]
    assert ray_tpu.get(theirs) == ["x"]
    assert ray_tpu.get(p.counts.remote()) == {"pushed": 2, "messages": 2}


def test_an_early_close_cancels_the_source(cluster):
    p = _producer()
    g = p.lane.options(num_returns="streaming").remote()
    _wait_for(lambda: ray_tpu.get(p.waiting.remote()) == 1)
    ray_tpu.get(p.step.remote(0))
    assert ray_tpu.get(next(g)) == 0
    g.close()
    _wait_for(lambda: "let go" in ray_tpu.get(p.read_log.remote()))
    ray_tpu.get(p.step.remote(1))  # put on a cancelled writer: dropped
    assert ray_tpu.get(p.counts.remote())["pushed"] == 1


def test_a_call_with_backpressure_is_pulled_and_holds_the_producer(cluster):
    p = _producer()
    g = p.numbers.options(num_returns="streaming",
                          generator_backpressure_num_objects=2).remote(50)
    assert ray_tpu.get(next(g)) == {"i": 0}
    time.sleep(1.0)
    log = ray_tpu.get(p.read_log.remote())
    assert log[0] == "pulled" and "pushed" not in log
    # two items beyond the one consumed, and the producer waits
    assert len(log) - 1 <= 4
    assert [ray_tpu.get(r)["i"] for r in g] == list(range(1, 50))
    assert ray_tpu.get(p.counts.remote()) == {"pushed": 0, "messages": 0}


def test_a_value_over_the_inline_threshold_comes_through_the_store(cluster):
    p = _producer()
    refs = list(p.blocks.options(num_returns="streaming").remote(3))
    rt = ray_tpu.core.api._runtime
    for i, ref in enumerate(refs):
        with rt._lock:
            held = rt._owned[ref.id.binary()]
        assert held.inline is None and held.location is not None
        assert held.size > INLINE_THRESHOLD
        arr = ray_tpu.get(ref)
        assert arr.shape == (1 << 16,) and arr[0] == i
    assert ray_tpu.get(p.counts.remote())["pushed"] == 3


def test_a_source_that_raises_fails_the_stream_past_its_items(cluster):
    @ray_tpu.remote
    class Failing:
        def stream(self):
            class Boom:
                def stream_to(self, writer):
                    writer.put("one")
                    raise ValueError("boom")

            return Boom()

    g = Failing.remote().stream.options(num_returns="streaming").remote()
    assert ray_tpu.get(next(g)) == "one"
    with pytest.raises(Exception, match="boom"):
        next(g)


def test_many_threads_put_and_nothing_is_lost_or_reordered():
    """The shipper alone, on a runtime that only records: more putting
    and flushing threads than cores under a short switch interval; every
    writer's items arrive once, in order, under contiguous indices, and
    `wait` returns only after its last item was sent."""
    from ray_tpu.core import serialization as ser
    from ray_tpu.core.stream_push import StreamShipper, StreamWriter
    from ray_tpu.core.worker_main import WorkerRuntime

    sent = []  # (owner, [(task_id, index, oid, value)])

    class Client:
        def send_oneway(self, owner, method, msg, frames=()):
            assert method == "stream_items"
            blob, off, got = memoryview(frames[0]), 0, []
            for task_id, index, oid, size, loc in msg["items"]:
                assert loc is None
                got.append((task_id, index, oid,
                            ser.loads(blob[off:off + size])))
                off += size
            sent.append((owner, got))

    class Runtime:
        client = Client()
        address = "here"
        stream_item_oid = staticmethod(WorkerRuntime.stream_item_oid)

    shipper = StreamShipper(Runtime())
    account = Account()
    n_threads, n_items = 32, 200
    writers = [StreamWriter(shipper, f"owner-{i % 3}", bytes([i]) * 16)
               for i in range(n_threads)]
    seen_at_wait = []

    def produce(writer):
        for i in range(n_items):
            writer.put((writer.task_id[0], i), account)
            if i % 7 == 0:
                writer.flush()
        writer.close()
        assert writer.wait()
        seen_at_wait.append(sum(
            1 for _, got in sent for t, *_ in got if t == writer.task_id))

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=produce, args=(w,))
                   for w in writers]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(interval)
    assert seen_at_wait == [n_items] * n_threads
    for w in writers:
        mine = [(index, oid, value) for owner, got in sent
                for task_id, index, oid, value in got
                if task_id == w.task_id]
        assert all(owner == w.owner for owner, got in sent
                   for task_id, *_ in got if task_id == w.task_id)
        assert [index for index, _, _ in mine] == list(range(n_items))
        assert [value for _, _, value in mine] == [
            (w.task_id[0], i) for i in range(n_items)]
        assert [oid for _, oid, _ in mine] == [
            WorkerRuntime.stream_item_oid(w.task_id, i)
            for i in range(n_items)]
        assert w.produced == n_items
    assert account.pushed == n_threads * n_items
    assert account.messages == len(sent) <= account.pushed
    # a put after the close is dropped, not sent
    writers[0].put("late")
    assert writers[0].produced == n_items


# ---------------------------------------------------------------------------
# the LLM deployment: pushed through a replica, pulled in process
# ---------------------------------------------------------------------------

ENGINE = {"block_size": 8, "num_blocks": 96, "max_model_len": 128,
          "max_batch_size": 8}


@pytest.fixture(scope="module")
def llm(cluster):
    from ray_tpu import serve
    from ray_tpu.serve.llm import build_llm_app

    handle = serve.run(build_llm_app(model="gpt2", preset="tiny",
                                     engine_config=ENGINE,
                                     max_ongoing_requests=16), name="llm")
    yield handle
    serve.delete("llm")


def _stats():
    from ray_tpu.util.state import llm_status

    (stats,) = llm_status("llm")
    return stats


def _prompt(n, seed):
    return np.random.RandomState(seed).randint(1, 500, size=n).tolist()


def test_pushed_through_a_replica_the_tokens_are_the_iterators(llm):
    """Greedy: the events a client gets, pushed, are the events the
    in-process iterator yields, pulled, field for field."""
    from ray_tpu.serve.llm import EngineConfig, LLMEngine, SamplingParams

    payload = {"prompt": _prompt(11, 3), "max_tokens": 9, "logprobs": True}
    before = _stats()["stream"]
    events = [ray_tpu.get(r, timeout=120) for r in
              llm.options(stream=True).remote(payload)]
    after = _stats()["stream"]
    assert after["pushed"] - before["pushed"] == 9
    assert after["items"] - before["items"] == 9
    assert 1 <= after["messages"] - before["messages"] <= 9

    engine = LLMEngine(EngineConfig(model="gpt2", preset="tiny", **ENGINE))
    stream = engine.add_request(payload["prompt"],
                                SamplingParams.from_payload(payload))
    while engine.step():
        pass
    pulled = list(stream) + [stream.final()]
    *tokens, final = events
    assert [e["token"] for e in tokens] == [e["token"] for e in pulled[:-1]]
    assert [e["index"] for e in tokens] == list(range(9))
    assert [sorted(e) for e in tokens] == [sorted(e) for e in pulled[:-1]]
    np.testing.assert_allclose([e["logprob"] for e in tokens],
                               [e["logprob"] for e in pulled[:-1]],
                               rtol=1e-4, atol=1e-5)
    assert final["done"] and final["token_ids"] == pulled[-1]["token_ids"]
    assert final["finish_reason"] == pulled[-1]["finish_reason"] == "length"
    # nothing went the old way in the engine next door either
    assert engine.stats()["stream"]["pushed"] == 0


def test_the_final_event_alone_when_the_payload_does_not_stream(llm):
    payload = {"prompt": _prompt(6, 4), "max_tokens": 5, "stream": False}
    before = _stats()["stream"]
    (final,) = [ray_tpu.get(r, timeout=120) for r in
                llm.options(stream=True).remote(payload)]
    assert final["done"] and final["num_generated"] == 5
    assert _stats()["stream"]["items"] == before["items"]


def test_concurrent_lanes_share_messages_and_the_count_identity_holds(llm):
    """gaps + burst + first tokens = tokens emitted = `stream.items`, as
    `tests/test_token_gap_account.py` has it of pulled streams."""
    before = _stats()
    n_req, n_tok = 6, 12
    gens = [llm.options(stream=True).remote(
        {"prompt": _prompt(5 + i, 10 + i), "max_tokens": n_tok})
        for i in range(n_req)]
    finals = []
    for g in gens:
        *tokens, final = [ray_tpu.get(r, timeout=120) for r in g]
        assert [e["index"] for e in tokens] == list(range(n_tok))
        assert [e["token"] for e in tokens] == final["token_ids"]
        finals.append(final)
    after = _stats()
    emitted = sum(f["num_generated"] for f in finals)
    assert emitted == n_req * n_tok

    def rose(path):
        a, b = after, before
        for key in path:
            a, b = a[key], b[key]
        return a - b if not isinstance(a, list) else sum(a) - sum(b)

    gaps = sum(rose(("token_gaps", "by_cause", cause))
               for cause in after["token_gaps"]["by_cause"])
    assert gaps + rose(("token_gaps", "burst")) + n_req == emitted
    assert rose(("stream", "items")) == emitted
    assert rose(("stream", "pushed")) == emitted
    assert rose(("stream", "handoff")) == emitted
    # six lanes decode together: fewer messages than items
    assert rose(("stream", "messages")) < emitted
    assert after["running"] == 0 and after["blocks_used"] == 0


def test_a_client_that_lets_go_frees_the_lane_and_its_pages(llm):
    """The cancelled source ends, and its `finally` aborts the request:
    the lane stops well short of its 110 tokens, and the pages are back."""
    before = _stats()
    g = llm.options(stream=True).remote(
        {"prompt": _prompt(7, 20), "max_tokens": 110})
    assert ray_tpu.get(next(g), timeout=120)["index"] == 0
    g.close()
    _wait_for(lambda: _stats()["finished_requests"]
              == before["finished_requests"] + 1)
    after = _stats()
    assert after["running"] == 0 and after["blocks_used"] == 0
    gaps = sum(sum(after["token_gaps"]["by_cause"][c])
               - sum(before["token_gaps"]["by_cause"][c])
               for c in after["token_gaps"]["by_cause"])
    assert gaps + 1 < 110


def test_with_backpressure_the_deployment_is_pulled(llm):
    before = _stats()["stream"]
    events = [ray_tpu.get(r, timeout=120) for r in
              llm.options(stream=True, generator_backpressure=2).remote(
                  {"prompt": _prompt(9, 30), "max_tokens": 7})]
    assert [e["index"] for e in events[:-1]] == list(range(7))
    assert events[-1]["done"]
    after = _stats()["stream"]
    assert after["pushed"] == before["pushed"]
    assert after["items"] - before["items"] == 7
