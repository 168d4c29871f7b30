"""Broadcast viewer plane (server/broadcaster.py — the round-13
tentpole): read-only viewers ride fan-out rooms, broadcast frames
serialize once per doc per tick, slow viewers lag-drop to a
snapshot+catch-up resync, join storms gate through the TokenBucket
reservation ladder, and presence is interest-sampled."""

from __future__ import annotations

import numpy as np
import pytest

from fluidframework_tpu.protocol.codec import (
    decode_body,
    decode_storm_push,
    is_storm_body,
    ops_event_encode_count,
    pack_map_words,
)
from fluidframework_tpu.protocol.messages import DocumentMessage, MessageType
from fluidframework_tpu.server.broadcaster import ViewerPlane
from fluidframework_tpu.server.kernel_host import KernelSequencerHost
from fluidframework_tpu.server.merge_host import KernelMergeHost
from fluidframework_tpu.server.routerlicious import RouterliciousService
from fluidframework_tpu.server.storm import StormController


def _storm_stack(num_docs: int = 4, **storm_kw):
    seq_host = KernelSequencerHost(num_slots=2,
                                   initial_capacity=max(4, num_docs))
    merge_host = KernelMergeHost(flush_threshold=10**9)
    service = RouterliciousService(merge_host=merge_host,
                                   batched_deli_host=seq_host,
                                   auto_pump=False)
    storm = StormController(service, seq_host, merge_host,
                            flush_threshold_docs=10**9, **storm_kw)
    return service, storm


def _words(k: int, seed: int = 0):
    return pack_map_words([0] * k, [(seed + i) % 16 for i in range(k)],
                          [7 + seed] * k).astype(np.uint32)


def _tick(storm, service, doc_clients, cseq0: int, k: int = 8,
          push=None, rid=0):
    entries = [[d, c, cseq0, 1, k] for d, c in doc_clients]
    payload = b"".join(_words(k, i).tobytes()
                       for i in range(len(doc_clients)))
    storm.submit_frame(push, {"rid": rid, "docs": entries},
                       memoryview(payload))
    storm.flush()


class _CollectingViewer:
    """In-process viewer transport: records every payload, decoding
    wire-shaped frames the way a socket client would."""

    def __init__(self):
        self.raw = []
        self.events = []

    def __call__(self, payload):
        self.raw.append(payload)
        if isinstance(payload, (bytes, bytearray)):
            self.events.append(decode_storm_push(payload)
                               if is_storm_body(payload)
                               else decode_body(payload))
        else:
            self.events.append(payload)

    def of(self, kind):
        return [e for e in self.events if isinstance(e, dict)
                and e.get("event") == kind]


class TestViewerStream:
    def test_viewer_receives_storm_tick_frames(self):
        service, storm = _storm_stack()
        writer = service.connect("doc", lambda m: None)
        service.pump()
        viewer = _CollectingViewer()
        conn = service.connect("doc", viewer, mode="viewer")
        assert conn.client_id.startswith("viewer-")
        assert conn.mode == "viewer"
        with pytest.raises(PermissionError):
            conn.submit([])

        _tick(storm, service, [("doc", writer.client_id)], cseq0=1)
        ticks = viewer.of("storm_tick")
        assert len(ticks) == 1
        t = ticks[0]
        assert t["doc"] == "doc" and t["n"] == 8
        assert t["last"] - t["first"] + 1 == 8
        assert list(t["words"]) == list(_words(8, 0))
        # Viewer connects never sequence a CLIENT_JOIN / enter the quorum
        # or connection map (no merge/ack bookkeeping at all).
        assert conn.client_id not in service._connections_for("doc")

        conn.close()
        _tick(storm, service, [("doc", writer.client_id)], cseq0=9)
        assert len(viewer.of("storm_tick")) == 1  # nothing after leave

    def test_serialize_once_invariant_encodes_per_tick_is_hot_docs(self):
        """THE acceptance invariant: broadcast encodes per tick == docs
        that ticked (with viewers), INDEPENDENT of viewer count."""
        service, storm = _storm_stack()
        docs = ["doc-a", "doc-b"]
        writers = {d: service.connect(d, lambda m: None).client_id
                   for d in docs}
        service.pump()
        viewers = [_CollectingViewer() for _ in range(64)]
        for i, v in enumerate(viewers):
            service.connect(docs[i % 2], v, mode="viewer")
        plane = service.viewers

        before = plane.stats["tick_encodes"]
        ticks = 3
        for t in range(ticks):
            _tick(storm, service, [(d, writers[d]) for d in docs],
                  cseq0=1 + t * 8)
        encodes = plane.stats["tick_encodes"] - before
        assert encodes == ticks * len(docs)  # NOT ticks * 64 viewers
        # Every viewer still received every tick of its doc — same bytes.
        for i, v in enumerate(viewers):
            frames = v.of("storm_tick")
            assert len(frames) == ticks
            assert all(f["doc"] == docs[i % 2] for f in frames)

    def test_per_op_path_shares_one_encode_per_batch(self):
        service = RouterliciousService()
        writer = service.connect("jdoc", lambda m: None)
        viewers = [_CollectingViewer() for _ in range(32)]
        for v in viewers:
            service.connect("jdoc", v, mode="viewer")
        before = ops_event_encode_count()
        writer.submit([DocumentMessage(
            type=MessageType.OPERATION, contents={"x": 1},
            client_sequence_number=1, reference_sequence_number=0)])
        service.pump()
        encodes = ops_event_encode_count() - before
        # One encode for the writer broadcast batch + one for the viewer
        # room — never one per subscriber.
        assert encodes <= 2
        for v in viewers:
            ops = v.of("ops")
            assert len(ops) >= 1


class TestLagDrop:
    def test_stalled_viewer_resyncs_without_stalling_writer(self):
        """A viewer whose transport backs up is LAG-DROPPED to a resync
        directive; the serving tick keeps acking the writer at full
        cadence, and healthy viewers keep streaming."""
        service, storm = _storm_stack()
        writer = service.connect("doc", lambda m: None)
        service.pump()
        healthy = _CollectingViewer()
        service.connect("doc", healthy, mode="viewer")
        stalled = _CollectingViewer()
        plane = service.viewers
        hello = plane.join("doc", stalled,
                           pending_probe=lambda: 10**9)  # transport full
        acks = []
        ticks = 4
        for t in range(ticks):
            _tick(storm, service, [("doc", writer.client_id)],
                  cseq0=1 + t * 8, push=acks.append, rid=t)
        # Writer path unaffected: every tick acked, fully sequenced.
        storm_acks = [a for a in acks if a.get("storm")]
        assert len(storm_acks) == ticks
        assert all(a["acks"][0][0] == 8 for a in storm_acks)
        # The stalled viewer was dropped once (not per tick) and told to
        # resync; the healthy viewer saw every tick.
        assert plane.stats["lag_drops"] == 1
        resyncs = stalled.of("viewer_resync")
        assert len(resyncs) == 1 and resyncs[0]["doc"] == "doc"
        assert len(healthy.of("storm_tick")) == ticks
        assert plane.room_size("doc") == 1

        # Resume re-enters the live stream (fresh subscriber, same id);
        # the gap up to resync["seq"] is the client's catch-up read.
        caught_up = service.get_deltas("doc", 0)
        seqs = [m.sequence_number for m in caught_up]
        # Contiguous through the whole gap (CLIENT_JOIN + every tick).
        assert seqs == list(range(seqs[0], seqs[0] + len(seqs)))
        assert seqs[-1] == 1 + ticks * 8
        resumed = plane.resume(hello["viewer_id"])
        # resync carried the stream position at DROP time; resume
        # returns the current head — the catch-up read covers between.
        assert resyncs[0]["seq"] == 9  # dropped during the first tick
        assert resumed["seq"] == 1 + ticks * 8
        stalled.raw.clear(), stalled.events.clear()
        stalled_probe_off = plane._viewers[hello["viewer_id"]]
        stalled_probe_off.pending_probe = None  # transport drained
        _tick(storm, service, [("doc", writer.client_id)],
              cseq0=1 + ticks * 8)
        assert len(stalled.of("storm_tick")) == 1

    def test_fanout_backlog_eviction_lag_drops(self):
        """The fan-out-queue side of lag detection: a viewer whose
        per-sub queue (the shallow viewer bound) overflows is evicted by
        the fan-out and lag-dropped at the next drain."""
        service = RouterliciousService()
        plane = ViewerPlane(service, max_lag_frames=4)
        v = _CollectingViewer()
        hello = plane.join("doc", v)
        sub = plane._viewers[hello["viewer_id"]].sub
        for i in range(6):  # overflow the shallow viewer bound
            plane.fanout.publish(plane._room("doc"), b"x%d" % i)
        assert plane.fanout.was_evicted(sub)
        plane._drain(["doc"])
        assert plane.stats["lag_drops"] == 1
        assert len(v.of("viewer_resync")) == 1

    def test_resync_gap_serves_from_cold_tier_without_hydrating(self):
        """The catch-up read a lag-dropped viewer performs rides the
        round-12 cold-read path: a doc evicted meanwhile serves its
        tick index from the cold head WITHOUT re-hydrating."""
        import tempfile

        from fluidframework_tpu.server.durable_store import GitSnapshotStore
        from fluidframework_tpu.server.residency import ResidencyManager
        tmp = tempfile.mkdtemp(prefix="viewer-cold-")
        service, storm = _storm_stack(
            spill_dir=f"{tmp}/spill", durability="group",
            snapshots=GitSnapshotStore(f"{tmp}/git"))
        res = ResidencyManager(storm, idle_evict_s=1e9)
        writer = service.connect("cdoc", lambda m: None)
        service.pump()
        _tick(storm, service, [("cdoc", writer.client_id)], cseq0=1)
        service.disconnect("cdoc", writer.client_id)
        service.pump()
        res.evict("cdoc")
        assert not res.is_resident("cdoc")
        caught_up = service.get_deltas("cdoc", 0)
        ops = [m for m in caught_up if m.type == MessageType.OPERATION]
        assert len(ops) == 8  # the tick's sequenced window, from cold
        assert not res.is_resident("cdoc")  # a READ must not hydrate
        storm._group_wal.close()


class TestJoinStorm:
    def test_join_storm_ladders_at_bucket_rate(self):
        """100k-viewer live-event start, miniaturized: every refused
        join reserves a claimable slot; retries at the hint drain the
        herd at exactly the bucket rate (no compounding debt)."""
        import heapq

        clk = [0.0]
        service = RouterliciousService()
        rate = 50.0
        plane = ViewerPlane(service, join_rate_per_s=rate,
                            clock=lambda: clk[0])
        n = 400
        events = [(0.0, i) for i in range(n)]
        heapq.heapify(events)
        admitted_at: dict[int, float] = {}
        while events:
            t, i = heapq.heappop(events)
            clk[0] = t
            retry = plane.admit_join("event-doc", f"client-{i}")
            if retry is None:
                admitted_at[i] = t
            else:
                heapq.heappush(events, (t + retry, i))
        assert len(admitted_at) == n
        per_sec: dict[int, int] = {}
        for t in admitted_at.values():
            per_sec[int(t)] = per_sec.get(int(t), 0) + 1
        assert max(per_sec.values()) <= rate + plane.joins.burst
        makespan = max(admitted_at.values())
        ideal = n / rate
        assert makespan <= ideal * 1.5  # converges near the drain rate

    def test_claimed_reservation_is_not_redebited(self):
        clk = [0.0]
        service = RouterliciousService()
        plane = ViewerPlane(service, join_rate_per_s=1.0, join_burst=1.0,
                            clock=lambda: clk[0])
        assert plane.admit_join("d", "a") is None  # burst slot
        retry = plane.admit_join("d", "b")
        assert retry is not None  # refused, slot reserved
        # Early return: the SAME slot stands (no new debit).
        early = plane.admit_join("d", "b")
        assert early == pytest.approx(retry, abs=1e-6)
        clk[0] = retry + 1e-6
        assert plane.admit_join("d", "b") is None  # claims the slot
        assert plane.stats["join_nacks"] == 2


class TestPresence:
    def test_interest_sampled_presence_bounded(self):
        """Viewers see a bounded roster sample + an exact count; joins
        past the sample bound never fan one event per member."""
        service = RouterliciousService()
        plane = ViewerPlane(service, roster_sample=8)
        viewers = []
        n = 200
        for i in range(n):
            v = _CollectingViewer()
            plane.join("big-doc", v)
            viewers.append(v)
        first_snapshot = viewers[-1].of("viewer_presence")[0]
        assert first_snapshot["total"] == n
        assert len(first_snapshot["sample"]) <= 8
        # Coalesced announces: O(log) per audience doubling, not O(n).
        assert plane.stats["presence_updates"] < 50
        # No per-join event per member: the FIRST viewer saw far fewer
        # presence frames than there were joins.
        assert len(viewers[0].of("viewer_presence")) < 60

    def test_writer_audience_roster_is_bounded(self):
        from fluidframework_tpu.server.audience import (
            announce_connect, roster_sample)

        class _Conn:
            def __init__(self, cid):
                self.client_id = cid
                self.mode = "write"
                self.signals = []

            def on_signal(self, s):
                self.signals.append(s)

        conns = {f"c{i}": _Conn(f"c{i}") for i in range(20)}
        members, total = roster_sample(conns, limit=5)
        assert len(members) == 5 and total == 20
        newcomer = _Conn("new")
        conns["new"] = newcomer
        announce_connect(conns, newcomer, max_roster=5)
        snap = newcomer.signals[0]["content"]
        assert snap["event"] == "snapshot"
        assert len(snap["members"]) == 5 and snap["total"] == 21
        # Past the bound: peers get ONE count update (totals must not
        # drift), never a per-join member event.
        for c in conns.values():
            if c is newcomer:
                continue
            events = [s["content"]["event"] for s in c.signals]
            assert events == ["count"]
            assert c.signals[0]["content"]["total"] == 21
        # And a leave past the bound is a count update naming the
        # leaver — the decrement side of the same drift fix.
        from fluidframework_tpu.server.audience import announce_leave
        del conns["new"]
        announce_leave(conns, "new", max_roster=5)
        last = conns["c0"].signals[-1]["content"]
        assert last["event"] == "count"
        assert last["total"] == 20 and last["left"] == "new"


class TestViewerOverAlfred:
    def test_viewer_stream_over_the_wire(self):
        """e2e through the asyncio front door: mode="viewer" hello, ops
        events on the live stream, get_deltas catch-up + viewer_resume
        (the ViewerStream resync dance) — all over a real socket."""
        import subprocess
        import sys
        import time

        from fluidframework_tpu.drivers.network_driver import (
            NetworkDocumentService, ViewerStream)
        proc = subprocess.Popen(
            [sys.executable, "-m", "fluidframework_tpu.server.alfred",
             "--port", "0", "--no-merge-host"],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
        try:
            line = proc.stdout.readline()
            assert line.startswith("READY "), (line, proc.stderr.read())
            port = int(line.split()[1])

            svc = NetworkDocumentService("127.0.0.1", port, "live-doc")
            stream = ViewerStream(svc)
            hello = stream.connect()
            assert hello["viewer"] is True
            assert hello["client_id"].startswith("viewer-")

            writer_svc = NetworkDocumentService("127.0.0.1", port,
                                                "live-doc")
            writer = writer_svc.connect(lambda m: None)
            writer.submit([DocumentMessage(
                type=MessageType.OPERATION, contents={"k": 1},
                client_sequence_number=1, reference_sequence_number=0)])
            deadline = time.monotonic() + 30
            while stream.stats["ops"] == 0 \
                    and time.monotonic() < deadline:
                time.sleep(0.02)
            assert stream.stats["ops"] >= 1
            assert stream.last_seq >= 1

            # The resync dance over the wire (catch-up + viewer_resume).
            stream.lagged = True
            stream.last_seq = 0
            caught_up = stream.resync()
            assert [m.sequence_number for m in caught_up]
            assert not stream.lagged
            writer_svc.close()
            svc.close()
        finally:
            proc.terminate()
            proc.wait(timeout=10)


class TestServicePlaneRetention:
    """Round-13 satellite: the in-process bus partitions + per-doc ops
    store take an opt-in retention horizon (without one, each cold doc
    leaves its whole message history in RAM)."""

    def test_bus_partitions_trim_below_slowest_group(self):
        from fluidframework_tpu.server.bus import Consumer, MessageBus
        bus = MessageBus(retention_messages=8)
        bus.create_topic("t", 1)
        fast = Consumer(bus, "t", "fast")
        slow = Consumer(bus, "t", "slow")
        for i in range(100):
            bus.produce("t", "k", i)
        msgs = fast.poll(0)
        fast.commit(0, msgs[-1].offset + 1)
        part = bus.topic("t").partitions[0]
        assert len(part.log) == 100  # the slow group pins the log
        half = slow.poll(0)[:50]
        slow.commit(0, half[-1].offset + 1)
        assert part.base == 50 and len(part.log) == 50
        slow.commit(0, 100)
        assert len(part.log) <= 8  # horizon tail retained
        # Reads from committed positions still work post-trim.
        for i in range(3):
            bus.produce("t", "k", 100 + i)
        assert [m.value for m in slow.poll(0)] == [100, 101, 102]

    def test_service_ops_store_horizon_bounds_history(self):
        service = RouterliciousService(ops_retention=16)
        writer = service.connect("rdoc", lambda m: None)
        for i in range(64):
            writer.submit([DocumentMessage(
                type=MessageType.OPERATION, contents={"i": i},
                client_sequence_number=i + 1,
                reference_sequence_number=0)])
        log = service.store.get("ops/rdoc", [])
        assert len(log) <= 32  # 2x horizon before each amortized trim
        # The tail stays contiguous and serves catch-up reads within
        # the horizon.
        tail = service.get_deltas("rdoc", log[0].sequence_number)
        seqs = [m.sequence_number for m in tail]
        assert seqs == list(range(seqs[0], seqs[0] + len(seqs)))

    def test_restored_offsets_pin_retention_after_restart(self, tmp_path):
        """A group with a DURABLE offset pins the retention floor from
        the moment the bus reopens — even before its Consumer
        re-attaches — so a late re-attach never finds its position
        trimmed out from under it."""
        from fluidframework_tpu.server.bus import Consumer
        from fluidframework_tpu.server.durable_store import DurableMessageBus
        bus = DurableMessageBus(tmp_path / "bus", retention_messages=4)
        bus.create_topic("t", 1)
        fast = Consumer(bus, "t", "fast")
        slow = Consumer(bus, "t", "slow")
        for i in range(50):
            bus.produce("t", "k", i)
        fast.commit(0, 50)
        slow.commit(0, 10)
        bus.close()

        bus2 = DurableMessageBus(tmp_path / "bus", retention_messages=4)
        bus2.create_topic("t", 1)
        # Only the fast group re-attaches and commits further...
        fast2 = Consumer(bus2, "t", "fast")
        for i in range(50, 60):
            bus2.produce("t", "k", i)
        fast2.commit(0, 60)
        # ...but "slow"'s durable offset (10) pinned the floor: its late
        # re-attach still reads from exactly where it left off.
        slow2 = Consumer(bus2, "t", "slow")
        values = [m.value for m in slow2.poll(0)]
        assert values[:3] == [10, 11, 12]
        assert len(values) == 50
        bus2.close()

    def test_bus_retention_keeps_service_plane_ram_bounded(self):
        """With the horizon on, a long op stream leaves O(horizon)
        messages in the bus partitions instead of O(history)."""
        from fluidframework_tpu.server.bus import MessageBus
        bus = MessageBus(retention_messages=32)
        service = RouterliciousService(bus=bus, ops_retention=32)
        writer = service.connect("bdoc", lambda m: None)
        for i in range(200):
            writer.submit([DocumentMessage(
                type=MessageType.OPERATION, contents={"i": i},
                client_sequence_number=i + 1,
                reference_sequence_number=0)])
        retained = sum(len(p.log) for t in bus._topics.values()
                       for p in t.partitions)
        assert retained <= 4 * 2 * 32 + 64  # partitions x topics x horizon
