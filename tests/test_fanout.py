"""Native fan-out service (§2.9 row 3 — Redis pub/sub +
redisSocketIoAdapter analog) and its broadcast integration."""

from __future__ import annotations

import pytest

from fluidframework_tpu.dds.map import SharedMap
from fluidframework_tpu.drivers.local_driver import LocalDocumentService
from fluidframework_tpu.native.fanout import PyFanout, make_fanout
from fluidframework_tpu.runtime.container import Container
from fluidframework_tpu.server.routerlicious import RouterliciousService


def _impls():
    impls = [PyFanout()]
    native = make_fanout()
    if native.is_native:
        impls.append(native)
    return impls


@pytest.mark.parametrize("fanout", _impls(),
                         ids=lambda f: "native" if f.is_native else "python")
class TestFanoutCore:
    def test_rooms_fifo_and_membership(self, fanout):
        a = fanout.connect()
        b = fanout.connect()
        fanout.join(a, "doc1")
        fanout.join(b, "doc1")
        fanout.join(b, "doc2")

        assert fanout.publish("doc1", b"m1") == 2
        assert fanout.publish("doc2", b"m2") == 1
        assert fanout.publish("nobody-home", b"m3") == 0

        assert fanout.pending(a) == 1
        assert fanout.poll(a) == b"m1"
        assert fanout.poll(a) is None
        assert [fanout.poll(b), fanout.poll(b)] == [b"m1", b"m2"]

        fanout.leave(a, "doc1")
        assert fanout.publish("doc1", b"m4") == 1  # only b now
        assert fanout.poll(b) == b"m4"

    def test_disconnect_cleans_rooms_and_queue(self, fanout):
        a = fanout.connect()
        fanout.join(a, "doc")
        fanout.publish("doc", b"x")
        fanout.disconnect(a)
        assert fanout.poll(a) is None
        assert fanout.publish("doc", b"y") == 0
        with pytest.raises(KeyError):
            fanout.join(a, "doc")

    def test_empty_payload_drains(self, fanout):
        a = fanout.connect()
        fanout.join(a, "empty-room")
        fanout.publish("empty-room", b"")
        fanout.publish("empty-room", b"after")
        assert fanout.poll(a) == b""   # empty payloads are legal...
        assert fanout.poll(a) == b"after"  # ...and must not wedge the queue
        assert fanout.poll(a) is None
        fanout.disconnect(a)

    def test_large_payload_roundtrip(self, fanout):
        a = fanout.connect()
        fanout.join(a, "big")
        payload = bytes(range(256)) * 4096  # 1 MiB binary
        assert fanout.publish("big", payload) == 1
        assert fanout.poll(a) == payload

    def test_delivered_total(self, fanout):
        before = fanout.delivered_total()
        a = fanout.connect()
        b = fanout.connect()
        fanout.join(a, "r")
        fanout.join(b, "r")
        fanout.publish("r", b"z")
        assert fanout.delivered_total() == before + 2

    def test_room_membership_under_churn(self, fanout):
        """Round-13 satellite: join/leave/disconnect interleavings keep
        room membership exact, empty rooms reclaim, and publishing to a
        dead subscriber's old room never wedges or miscounts."""
        rooms_before = fanout.room_count()
        subs = [fanout.connect() for _ in range(8)]
        for i, sub in enumerate(subs):
            fanout.join(sub, "churn-a")
            if i % 2:
                fanout.join(sub, "churn-b")
        assert fanout.room_size("churn-a") == 8
        assert fanout.room_size("churn-b") == 4
        assert fanout.room_count() == rooms_before + 2

        # Interleave: leave a, disconnect mid-membership, re-join.
        fanout.leave(subs[0], "churn-a")
        fanout.disconnect(subs[1])  # was in both rooms
        fanout.join(subs[0], "churn-b")
        assert fanout.room_size("churn-a") == 6
        assert fanout.room_size("churn-b") == 4  # -subs[1] +subs[0]

        # Publish-to-dead-subscriber: disconnect then publish — dead
        # members are skipped, live members still count exactly.
        fanout.disconnect(subs[2])
        assert fanout.publish("churn-a", b"alive") == 5
        assert fanout.poll(subs[3]) == b"alive"
        # A dead sub cannot re-join and polls nothing.
        with pytest.raises(KeyError):
            fanout.join(subs[1], "churn-a")
        assert fanout.poll(subs[1]) is None

        # Empty-room reclamation: drain every member out both ways.
        for sub in subs:
            fanout.leave(sub, "churn-a")  # no-op for gone members
            fanout.disconnect(sub)
        assert fanout.room_size("churn-a") == 0
        assert fanout.room_size("churn-b") == 0
        assert fanout.room_count() == rooms_before
        assert fanout.publish("churn-a", b"nobody") == 0

    def test_per_subscriber_queue_limit(self, fanout):
        """Per-room outbox bounds: a shallow-limit subscriber (the
        viewer class) evicts early; default-limit peers are untouched;
        resetting the limit restores the default."""
        viewer = fanout.connect()
        writer = fanout.connect()
        fanout.join(viewer, "lim")
        fanout.join(writer, "lim")
        fanout.set_queue_limit(viewer, 3)
        for i in range(5):
            fanout.publish("lim", b"m%d" % i)
        assert fanout.was_evicted(viewer)
        assert not fanout.was_evicted(writer)
        assert fanout.pending(writer) == 5
        with pytest.raises(KeyError):
            fanout.set_queue_limit(viewer, None)  # evicted = unknown
        # A fresh subscriber with the limit RESET takes the default.
        fresh = fanout.connect()
        fanout.join(fresh, "lim")
        fanout.set_queue_limit(fresh, 2)
        fanout.set_queue_limit(fresh, None)
        for i in range(4):
            fanout.publish("lim", b"x")
        assert not fanout.was_evicted(fresh)
        fanout.disconnect(viewer)
        fanout.disconnect(writer)
        fanout.disconnect(fresh)

    def test_publish_batch_matches_sequential_publishes(self, fanout):
        """One batched call == the same per-room publishes, in order —
        the O(batch) broadcast hop of a storm tick."""
        a = fanout.connect()
        b = fanout.connect()
        fanout.join(a, "batch-1")
        fanout.join(b, "batch-1")
        fanout.join(b, "batch-2")
        delivered = fanout.publish_batch([
            ("batch-1", b"\x00storm1:8:1"),
            ("batch-2", b"\x00storm9:16:2"),
            ("batch-empty-room", b"zzz"),
            ("batch-1", b""),  # empty payloads stay legal in a batch
        ])
        assert delivered == 2 + 1 + 0 + 2
        assert fanout.poll(a) == b"\x00storm1:8:1"
        assert fanout.poll(a) == b""
        assert [fanout.poll(b) for _ in range(3)] == [
            b"\x00storm1:8:1", b"\x00storm9:16:2", b""]
        assert fanout.publish_batch([]) == 0
        fanout.disconnect(a)
        fanout.disconnect(b)


@pytest.mark.parametrize("fanout", _impls(),
                         ids=lambda f: "native" if f.is_native else "python")
def test_slow_consumer_evicted(fanout):
    # A subscriber that never polls is dropped once MAX_QUEUE payloads
    # queue up (socket.io Redis-adapter slow-client semantics) instead of
    # buffering without bound; healthy subscribers are untouched.
    from fluidframework_tpu.native import fanout as fanout_mod
    slow = fanout.connect()
    ok = fanout.connect()
    fanout.join(slow, "room")
    fanout.join(ok, "room")
    limit = fanout_mod.MAX_QUEUE
    for i in range(limit + 2):
        fanout.publish("room", b"p")
        if fanout.poll(ok) is None:  # ok drains as it goes
            raise AssertionError("healthy subscriber starved")
    assert fanout.was_evicted(slow)
    assert not fanout.was_evicted(ok)
    assert fanout.poll(slow) is None
    # The room still works for the healthy subscriber.
    assert fanout.publish("room", b"tail") == 1
    assert fanout.poll(ok) == b"tail"
    # Disconnecting the evicted sub succeeds and clears the flag (the
    # eviction set must not grow forever).
    fanout.disconnect(slow)
    assert not fanout.was_evicted(slow)


def test_native_fanout_builds_here():
    # This image has the toolchain; the native path must actually build
    # (elsewhere make_fanout falls back to the Python twin).
    assert make_fanout().is_native


@pytest.mark.parametrize("force_python", [True, False])
def test_service_broadcast_through_fanout(force_python):
    service = RouterliciousService(fanout=make_fanout(force_python))

    def make_doc(doc_id):
        svc = LocalServiceAdapter(service, doc_id)
        container = Container.create_detached(svc)
        ds = container.runtime.create_datastore("default")
        ds.create_channel("root", SharedMap.channel_type)
        container.attach()
        return container

    # The local driver duck-types over any service with the front-door
    # surface; RouterliciousService has it.
    class LocalServiceAdapter(LocalDocumentService):
        pass

    c1 = make_doc("doc")
    c2 = Container.load(LocalServiceAdapter(service, "doc"))
    m1 = c1.runtime.get_datastore("default").get_channel("root")
    m2 = c2.runtime.get_datastore("default").get_channel("root")
    m1.set("x", 1)
    m2.set("y", 2)
    assert m1.get("y") == 2 and m2.get("x") == 1
    assert service.fanout.delivered_total() > 0

    # Disconnect stops delivery to that subscriber but not others.
    c2.close()
    m1.set("z", 3)
    assert m1.get("z") == 3


# -- the writers' drain (RouterliciousService._drain_fanout) -----------------


class _CountingFanout:
    """Delegates to a fan-out and counts the drain's native calls."""

    def __init__(self, inner):
        self.inner = inner
        self.calls = {"poll": 0, "was_evicted": 0, "poll_batch": 0}

    def poll(self, sub):
        self.calls["poll"] += 1
        return self.inner.poll(sub)

    def was_evicted(self, sub):
        self.calls["was_evicted"] += 1
        return self.inner.was_evicted(sub)

    def poll_batch(self, subs):
        self.calls["poll_batch"] += 1
        return self.inner.poll_batch(subs)

    def __getattr__(self, name):
        return getattr(self.inner, name)


def _drain_service(force_python):
    from fluidframework_tpu.utils.telemetry import CollectingLogger

    fanout = _CountingFanout(make_fanout(force_python))
    return RouterliciousService(fanout=fanout, auto_pump=False,
                                logger=CollectingLogger())


def _writer(service, doc_id):
    """Connect one writer whose handler records each batch's seqs; the
    joins are drained, so the record starts empty."""
    calls = []
    conn = service.connect(doc_id, lambda batch: calls.append(
        [m.sequence_number for m in batch]))
    service.pump()
    calls.clear()
    return conn, calls


def _publish_op(service, doc_id, seq):
    import json

    from fluidframework_tpu.protocol.codec import to_wire
    from fluidframework_tpu.protocol.messages import (
        MessageType, SequencedDocumentMessage)
    op = SequencedDocumentMessage(
        client_id="peer", sequence_number=seq, minimum_sequence_number=0,
        client_sequence_number=seq, reference_sequence_number=0,
        type=MessageType.OPERATION, contents={"seq": seq})
    service.fanout.publish(doc_id, json.dumps(to_wire(op)).encode())


_DRAIN_IMPLS = pytest.mark.parametrize("force_python", [True, False],
                                       ids=["python", "native"])


@_DRAIN_IMPLS
def test_drain_delivers_queued_ops_fifo_in_one_call(force_python):
    service = _drain_service(force_python)
    _conn, calls = _writer(service, "fifo")
    for seq in (100, 101, 102):
        _publish_op(service, "fifo", seq)
    assert service._drain_fanout() == 3
    assert calls == [[100, 101, 102]]


@_DRAIN_IMPLS
def test_drain_drops_replayed_ops(force_python):
    service = _drain_service(force_python)
    _conn, calls = _writer(service, "dedup")
    _publish_op(service, "dedup", 100)
    _publish_op(service, "dedup", 101)
    assert service._drain_fanout() == 2
    for seq in (100, 101, 102):  # crash replay re-publishes 100 and 101
        _publish_op(service, "dedup", seq)
    assert service._drain_fanout() == 1
    assert calls == [[100, 101], [102]]


@_DRAIN_IMPLS
def test_drain_skips_compact_storm_frames(force_python):
    service = _drain_service(force_python)
    conn, calls = _writer(service, "compact")
    sub = service._fanout_subs[("compact", conn.client_id)]
    service.fanout.publish("compact", b"\x00storm1:8:1")
    passes = service.metrics.counter("fanout.drain.passes").value
    payloads = service.metrics.counter("fanout.drain.payloads").value
    assert service._drain_fanout() == 0
    assert calls == []
    assert service.fanout.pending(sub) == 0
    assert service.metrics.counter("fanout.drain.passes").value \
        == passes + 2
    assert service.metrics.counter("fanout.drain.payloads").value \
        == payloads + 1


@_DRAIN_IMPLS
def test_drain_disconnects_evicted_writer(force_python):
    service = _drain_service(force_python)
    slow, slow_calls = _writer(service, "evict")
    ok, ok_calls = _writer(service, "evict")
    slow_calls.clear()  # the room-mate's join
    service.fanout.set_queue_limit(
        service._fanout_subs[("evict", slow.client_id)], 2)
    for _ in range(3):  # the third publish passes the bound
        service.fanout.publish("evict", b"\x00storm1:8:1")
    _publish_op(service, "evict", 100)
    assert service._drain_fanout() == 1
    assert ok_calls == [[100]] and slow_calls == []
    assert not slow.open
    assert ("evict", slow.client_id) not in service._fanout_subs
    events = service.logger.matching("FanoutSubscriberEvicted")
    assert [e["clientId"] for e in events] == [slow.client_id]
    _publish_op(service, "evict", 101)
    assert service._drain_fanout() == 1
    assert ok_calls == [[100], [101]] and slow_calls == []


@_DRAIN_IMPLS
def test_drain_follows_connects_and_disconnects(force_python):
    service = _drain_service(force_python)
    a, a_calls = _writer(service, "churn")
    _publish_op(service, "churn", 100)
    assert service._drain_fanout() == 1
    b, b_calls = _writer(service, "churn")  # joins after the first drain
    a_calls.clear()
    _publish_op(service, "churn", 200)
    assert service._drain_fanout() == 2
    assert a_calls == [[200]] and b_calls == [[200]]
    a.close()
    _publish_op(service, "churn", 300)
    assert service._drain_fanout() == 1
    assert a_calls == [[200]] and b_calls == [[200], [300]]
    assert service.logger.matching("FanoutSubscriberEvicted") == []


@_DRAIN_IMPLS
def test_drain_of_compact_frames_is_two_batched_calls(force_python):
    """1,024 writers holding one compact frame each: one pump drains them
    in one pass that pops every head and one that finds nothing — no
    per-subscriber poll or eviction probe."""
    service = _drain_service(force_python)
    docs = [f"many-{i}" for i in range(256)]
    for doc_id in docs:
        for _ in range(4):
            service.connect(doc_id, lambda batch: None)
    service.pump()
    assert len(service._fanout_subs) == 1024
    fanout = service.fanout
    fanout.publish_batch([(d, b"\x00storm1:8:1") for d in docs])
    fanout.calls = dict.fromkeys(fanout.calls, 0)
    passes = service.metrics.counter("fanout.drain.passes").value
    payloads = service.metrics.counter("fanout.drain.payloads").value
    service.pump()
    assert service.metrics.counter("fanout.drain.passes").value \
        == passes + 2
    assert service.metrics.counter("fanout.drain.payloads").value \
        == payloads + 1024
    assert fanout.calls == {"poll": 0, "was_evicted": 0, "poll_batch": 2}
