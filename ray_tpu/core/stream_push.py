"""Pushed streams: the second kind of producer behind a streaming call.

A streaming method usually returns a generator, and the worker's request
thread PULLS it: one wake, one serialisation and one `stream_item` message
a value (`WorkerRuntime._run_stream`). A producer that makes the values of
many streams in one step of one loop (the LLM engine: a token a lane) can
return a **stream source** instead: any object with a
`stream_to(writer)` method, which is told a `StreamWriter` and blocks
until its stream is over. What the producer's loop `put`s on its writers
during a step it sends with one `flush` at the step's end, on its own
thread: everything put since the last flush, by any writer of the process,
one `stream_items` message an owner. The request's thread sleeps from the
start of its stream to its end, and no thread is woken a token.

A source is iterable too: a call that asks for backpressure
(`generator_backpressure_num_objects`) and the local-mode runtime iterate
it as they would a generator, because a push that must not block the
producer's loop cannot honour a consumer's pace.
"""

from __future__ import annotations

import contextlib
import threading
import time

from ray_tpu.core import serialization as ser
from ray_tpu.core.specs import INLINE_THRESHOLD

_CLOSE = object()  # a writer's last entry: nothing follows it


def is_stream_source(obj) -> bool:
    return hasattr(obj, "stream_to")


class StreamWriter:
    """One stream's way out of the process. `put` may be called from any
    thread and never blocks: it is an append. Nothing leaves before a
    `flush` (or the `close`, which flushes): then items leave in the
    order they were put, under the indices and the deterministic oids a
    pulled stream would have given them."""

    def __init__(self, shipper: "StreamShipper", owner: str,
                 task_id: bytes):
        self._shipper = shipper
        self.owner = owner
        self.task_id = task_id
        self.produced = 0      # items accepted: the next item's index
        self.closed = False    # nothing more is accepted
        self.cancelled = False  # the consumer let go of the stream
        self.error: BaseException | None = None  # an item did not ship
        self._over = threading.Event()

    def put(self, value, observer=None) -> None:
        """`observer`, where given, is told of the message the value
        leaves in: `observer.shipping(put_times)` is entered when a
        flush takes up the items of one message that named it (their
        `perf_counter` readings at `put`), and left once the message is
        handed to the socket."""
        self._shipper._put(self, value, observer)

    def flush(self) -> None:
        """Send, on the calling thread, everything put so far by every
        writer of the process: one message an owner. A producer's loop
        calls it once a step, on any one of the step's writers."""
        self._shipper.flush()

    def close(self) -> None:
        """No more items; what is pending is flushed."""
        if self._shipper._put(self, _CLOSE, None):
            self._shipper.flush()

    def wait(self) -> bool:
        """Block until the stream is over: closed and every item sent
        (True), or cancelled by its consumer or failed (False)."""
        self._over.wait()
        return not (self.cancelled or self.error is not None)

    def cancel(self) -> None:
        self.cancelled = True
        self.closed = True
        self._over.set()

    # an entry of `WorkerRuntime._active_streams` is `set()` by
    # `_h_stream_cancel`, a generator's cancel event and a writer alike
    set = cancel

    def _fail(self, error: BaseException) -> None:
        self.error = error
        self.closed = True
        self._over.set()


class StreamShipper:
    """What a worker process's writers put down, and the one sender of
    it: whoever flushes sends for all."""

    def __init__(self, runtime):
        self._rt = runtime
        self._lock = threading.Lock()
        self._pending: list = []  # (writer, index, value, observer, t_put)
        # held from taking the pending items up to their last send, so
        # that two flushing threads cannot reorder a stream's items
        self._ship_lock = threading.Lock()

    def _put(self, writer: StreamWriter, value, observer) -> bool:
        """Whether the writer still took it."""
        now = time.perf_counter()
        with self._lock:
            if writer.closed:
                return False
            if value is _CLOSE:
                writer.closed = True
                index = -1
            else:
                index = writer.produced
                writer.produced += 1
            self._pending.append((writer, index, value, observer, now))
        return True

    def flush(self) -> None:
        with self._ship_lock:
            with self._lock:
                batch, self._pending = self._pending, []
            by_owner: dict[str, list] = {}
            for entry in batch:
                by_owner.setdefault(entry[0].owner, []).append(entry)
            for owner, entries in by_owner.items():
                try:
                    self._ship(owner, entries)
                except Exception as e:  # noqa: BLE001
                    # not the flushing loop's fault: these streams fail,
                    # each on its own request thread
                    for entry in entries:
                        entry[0]._fail(e)

    def _ship(self, owner: str, entries: list) -> None:
        """One `stream_items` message: the items' ids in the header, their
        inline payloads end to end in one frame (on this sandbox's CPU a
        frame an item cost a fifth more a message of seven)."""
        observed: dict = {}  # observer -> its items' put times
        for _, index, _, observer, t_put in entries:
            if observer is not None and index >= 0:
                observed.setdefault(observer, []).append(t_put)
        items, payloads, closing = [], [], []
        with contextlib.ExitStack() as told:
            for observer, put_times in observed.items():
                told.enter_context(observer.shipping(put_times))
            for writer, index, value, _, _ in entries:
                if value is _CLOSE:
                    closing.append(writer)
                    continue
                if writer.cancelled or writer.error is not None:
                    continue
                try:
                    oid = self._rt.stream_item_oid(writer.task_id, index)
                    payload, loc = self._pack(oid, value)
                except Exception as e:  # noqa: BLE001
                    writer._fail(e)  # the request's thread raises it
                    continue
                items.append((writer.task_id, index, oid, len(payload), loc))
                payloads.append(payload)
            if items:
                self._rt.client.send_oneway(
                    owner, "stream_items",
                    {"items": items, "producer": self._rt.address},
                    frames=[b"".join(payloads)])
        for writer in closing:
            writer._over.set()

    def _pack(self, oid: bytes, value) -> tuple[bytes, dict | None]:
        """(inline payload, None), or (b"", where the store holds it) for
        a value over INLINE_THRESHOLD, as `_run_stream` ships one."""
        head_payload, views, total = ser.serialize(value)
        if total > INLINE_THRESHOLD:
            rt = self._rt
            loc = {"address": rt.nodelet_address,
                   "store_name": rt.store.name, "size": total}
            try:
                mv = rt.store.create(oid, total)
                ser.write_into(mv, head_payload, views)
                del mv
                rt.store.seal(oid)
                return b"", loc
            except KeyError:  # already present (retry replay)
                return b"", loc
            except Exception:  # noqa: BLE001 — store full: ship inline
                pass
        buf = bytearray(total)
        ser.write_into(memoryview(buf), head_payload, views)
        return bytes(buf), None
