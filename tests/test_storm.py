"""Columnar op-storm fast path (server/storm.py): the batched-cadence
deli → merger pipeline fused into one device tick, fed by binary frames.

Oracles: (1) the device map state must equal a scalar MapData replay of
the messages the catch-up read path materializes from the columnar
durable records; (2) resending an un-acked frame must be fully ignored
(kernel clientSequenceNumber dedup — at-least-once delivery contract);
(3) unknown writers are rejected by the sequencer kernel, not trusted.
"""

import json
import socket
import struct

import numpy as np
import pytest

from fluidframework_tpu.dds.map_data import MapData
from fluidframework_tpu.protocol.codec import (
    decode_storm_body,
    decode_storm_push,
    encode_storm_body,
    encode_storm_frame,
    is_storm_body,
)
from fluidframework_tpu.protocol.messages import MessageType
from fluidframework_tpu.server.kernel_host import KernelSequencerHost
from fluidframework_tpu.server.merge_host import KernelMergeHost
from fluidframework_tpu.server.routerlicious import RouterliciousService
from fluidframework_tpu.server.storm import StormController


def make_service(num_docs=8, flush_threshold_docs=10**9, **storm_kw):
    seq_host = KernelSequencerHost(num_slots=2, initial_capacity=num_docs)
    merge_host = KernelMergeHost(flush_threshold=10**9)
    service = RouterliciousService(merge_host=merge_host,
                                   batched_deli_host=seq_host,
                                   auto_pump=False)
    storm = StormController(service, seq_host, merge_host,
                            flush_threshold_docs=flush_threshold_docs,
                            **storm_kw)
    return service, storm, merge_host


def join_docs(service, docs):
    clients = {d: service.connect(d, lambda m: None).client_id
               for d in docs}
    service.pump()
    return clients


def make_words(rng, k, num_slots=16):
    kinds = rng.choice([0, 0, 0, 1, 2], size=k).astype(np.uint32)
    slots = rng.integers(0, num_slots, k).astype(np.uint32)
    vals = rng.integers(0, 1 << 20, k).astype(np.uint32)
    return (kinds | (slots << 2) | (vals << 12)).astype(np.uint32)


def read_push(sock):
    """One server push off the wire: binary storm acks decode through
    the codec; JSON control frames through json."""
    length = struct.unpack(">I", sock.recv(4, socket.MSG_WAITALL))[0]
    body = sock.recv(length, socket.MSG_WAITALL)
    if is_storm_body(body):
        return decode_storm_push(body)
    return json.loads(body.decode())


def replay_oracle(service, doc_id):
    """Scalar MapData fold of the materialized catch-up messages."""
    data = MapData()
    for m in service.get_deltas(doc_id, 0):
        if m.type != MessageType.OPERATION or not isinstance(m.contents,
                                                             dict):
            continue
        inner = m.contents.get("contents", {}).get("contents")
        if inner:
            data.process(inner, False, None)
    return dict(data.items())


def test_codec_roundtrip():
    words = np.arange(7, dtype=np.uint32)
    body = encode_storm_body({"op": "storm", "docs": []}, words.tobytes())
    assert is_storm_body(body)
    header, payload = decode_storm_body(body)
    assert header["op"] == "storm"
    assert np.array_equal(np.frombuffer(payload, np.uint32), words)
    assert not is_storm_body(b'{"op": "connect"}')


def test_storm_matches_scalar_replay_and_acks():
    docs = [f"doc{i}" for i in range(8)]
    service, storm, merge_host = make_service()
    clients = join_docs(service, docs)
    rng = np.random.default_rng(0)
    k = 64
    acks = []
    cseqs = {d: 1 for d in docs}
    for _tick in range(3):
        payload, hdr_docs = b"", []
        for d in docs:
            w = make_words(rng, k)
            payload += w.tobytes()
            hdr_docs.append([d, clients[d], cseqs[d], 1, k])
            cseqs[d] += k
        storm.submit_frame(acks.append, {"op": "storm", "rid": _tick,
                                         "docs": hdr_docs},
                           memoryview(payload))
    storm.flush()
    assert storm.stats["sequenced_ops"] == len(docs) * k * 3
    assert len(acks) == 3
    for ack in acks:
        assert all(a[0] == k for a in ack["acks"])
    for d in docs:
        assert merge_host.map_entries(d, "default", "root") \
            == replay_oracle(service, d), d


def test_storm_resend_is_ignored_not_reapplied():
    docs = ["doc0", "doc1"]
    service, storm, merge_host = make_service()
    clients = join_docs(service, docs)
    k = 16
    words = make_words(np.random.default_rng(1), k)
    hdr = {"op": "storm", "rid": 1,
           "docs": [[d, clients[d], 1, 1, k] for d in docs]}
    for _ in range(2):  # first send + verbatim resend (no ack seen)
        storm.submit_frame(None, dict(hdr), memoryview(words.tobytes() * 2))
        storm.flush()
    assert storm.stats["sequenced_ops"] == len(docs) * k
    assert storm.stats["nacked_or_ignored_ops"] == len(docs) * k
    for d in docs:
        assert merge_host.map_entries(d, "default", "root") \
            == replay_oracle(service, d)


def test_storm_unknown_writer_rejected_by_kernel():
    service, storm, merge_host = make_service()
    join_docs(service, ["doc0"])
    k = 8
    words = make_words(np.random.default_rng(2), k)
    acks = []
    storm.submit_frame(acks.append, {
        "op": "storm", "rid": 9,
        "docs": [["doc0", "client-never-joined", 1, 1, k]],
    }, memoryview(words.tobytes()))
    storm.flush()
    assert acks[0]["acks"][0][0] == 0  # zero ops sequenced
    assert storm.stats["sequenced_ops"] == 0
    assert merge_host.map_entries("doc0", "default", "root") == {}


def test_storm_and_dict_paths_share_the_sequencer_state():
    """Per-doc total order is ONE stream: ops submitted through the
    regular front door and storm ops interleave with strictly increasing
    seqs."""
    service, storm, merge_host = make_service()
    docs = ["doc0"]
    clients = join_docs(service, docs)
    k = 8
    words = make_words(np.random.default_rng(3), k)
    storm.submit_frame(None, {"op": "storm", "docs": [
        ["doc0", clients["doc0"], 1, 1, k]]}, memoryview(words.tobytes()))
    storm.flush()
    msgs = service.get_deltas("doc0", 0)
    seqs = [m.sequence_number for m in msgs]
    assert seqs == sorted(seqs) and len(set(seqs)) == len(seqs)
    # join (seq 1) + k storm ops
    assert len([m for m in msgs if m.type == MessageType.OPERATION]) == k


def test_storm_channel_rejects_dict_traffic():
    from fluidframework_tpu.protocol.messages import (
        SequencedDocumentMessage,
    )

    service, storm, merge_host = make_service()
    clients = join_docs(service, ["doc0"])
    words = make_words(np.random.default_rng(4), 4)
    storm.submit_frame(None, {"op": "storm", "docs": [
        ["doc0", clients["doc0"], 1, 1, 4]]}, memoryview(words.tobytes()))
    storm.flush()
    dict_op = SequencedDocumentMessage(
        client_id=clients["doc0"], sequence_number=10**6,
        minimum_sequence_number=0, client_sequence_number=99,
        reference_sequence_number=1, type=MessageType.OPERATION,
        contents={"address": "default",
                  "contents": {"address": "root",
                               "contents": {"type": "set", "key": "x",
                                            "value": 1}}},
        timestamp=0, data=None)
    with pytest.raises(ValueError, match="storm-served"):
        merge_host.ingest("doc0", dict_op)


def test_storm_over_bridge_wire():
    from fluidframework_tpu.server.bridge_host import BridgeFrontDoor

    docs = [f"d{i}" for i in range(4)]
    service, storm, merge_host = make_service(
        flush_threshold_docs=len(docs))
    front = BridgeFrontDoor(service, 0)
    try:
        clients = join_docs(service, docs)
        sock = socket.create_connection(("127.0.0.1", front.port))
        sock.settimeout(30)
        k = 32
        words = (np.arange(k, dtype=np.uint32) << 12)
        hdr = {"op": "storm", "rid": 7,
               "docs": [[d, clients[d], 1, 1, k] for d in docs]}
        sock.sendall(encode_storm_frame(hdr, words.tobytes() * len(docs)))
        ack = read_push(sock)
        assert ack["rid"] == 7 and all(a[0] == k for a in ack["acks"])
        for d in docs:
            assert merge_host.map_entries(d, "default", "root") \
                == {"k0": k - 1}  # LWW: the last set wins
        sock.close()
    finally:
        front.close()


def test_malformed_storm_frames_fail_alone():
    """Bad frames are rejected BEFORE buffering (never poisoning other
    sessions' frames) and the socket answers with an error and lives."""
    from fluidframework_tpu.server.bridge_host import BridgeFrontDoor

    service, storm, merge_host = make_service(flush_threshold_docs=1)
    front = BridgeFrontDoor(service, 0)
    try:
        clients = join_docs(service, ["doc0"])
        sock = socket.create_connection(("127.0.0.1", front.port))
        sock.settimeout(30)

        def roundtrip(hdr, payload):
            sock.sendall(encode_storm_frame(hdr, payload))
            return read_push(sock)

        w4 = np.zeros(4, np.uint32).tobytes()
        # count exceeding the payload
        resp = roundtrip({"op": "storm", "rid": 1,
                          "docs": [["doc0", clients["doc0"], 1, 1, 99]]},
                         w4)
        assert "error" in resp
        # repeated doc within one frame
        resp = roundtrip({"op": "storm", "rid": 2,
                          "docs": [["doc0", clients["doc0"], 1, 1, 4],
                                   ["doc0", clients["doc0"], 5, 1, 4]]},
                         w4 * 2)
        assert "error" in resp
        # key slot out of the configured range
        big_slot = np.full(4, np.uint32(1000 << 2), np.uint32)
        resp = roundtrip({"op": "storm", "rid": 3,
                          "docs": [["doc0", clients["doc0"], 1, 1, 4]]},
                         big_slot.tobytes())
        assert "error" in resp
        # negative count must not slip through np.frombuffer
        resp = roundtrip({"op": "storm", "rid": 4,
                          "docs": [["doc0", clients["doc0"], 1, 1, -1]]},
                         w4)
        assert "error" in resp
        # ...and the connection still works for a GOOD frame.
        resp = roundtrip({"op": "storm", "rid": 5,
                          "docs": [["doc0", clients["doc0"], 1, 1, 4]]},
                         np.full(4, 9 << 12, np.uint32).tobytes())
        assert resp.get("storm") and resp["acks"][0][0] == 4
        assert storm.stats["sequenced_ops"] == 4
        sock.close()
    finally:
        front.close()


def test_storm_tail_frame_drains_on_idle():
    """A frame below the tick threshold must still sequence (bridge idle
    drain) rather than starve waiting for a full cohort."""
    import time

    from fluidframework_tpu.server.bridge_host import BridgeFrontDoor

    service, storm, merge_host = make_service(flush_threshold_docs=1000)
    front = BridgeFrontDoor(service, 0)
    try:
        clients = join_docs(service, ["doc0"])
        sock = socket.create_connection(("127.0.0.1", front.port))
        sock.settimeout(30)
        words = np.full(4, 7 << 12, np.uint32)
        sock.sendall(encode_storm_frame(
            {"op": "storm", "rid": 1,
             "docs": [["doc0", clients["doc0"], 1, 1, 4]]},
            words.tobytes()))
        ack = read_push(sock)
        assert ack["acks"][0][0] == 4
        sock.close()
    finally:
        front.close()


def test_pipeline_depth_streams_acks_behind_compute():
    """pipeline_depth > 1 with a STREAMING sender (not ack-gated): acks
    lag by <= depth ticks while in flight, every frame is eventually
    acked exactly once, and the map state equals the scalar replay."""
    service, storm, merge_host = make_service(flush_threshold_docs=2,
                                              pipeline_depth=3)
    docs = ["a", "b"]
    clients = join_docs(service, docs)
    rng = np.random.default_rng(5)
    acks = []
    k = 8
    n_ticks = 6
    for t in range(n_ticks):
        header = {"rid": t, "docs": [[d, clients[d], 1 + t * k, 1, k]
                                     for d in docs]}
        payload = b"".join(make_words(rng, k).tobytes() for _ in docs)
        # submit_frame auto-flushes at the 2-doc threshold: each frame
        # IS one tick.
        storm.submit_frame(acks.append, header, memoryview(payload))
        # Acks really are deferred: exactly `depth` ticks stay in
        # flight, and only the ticks behind them have acked.
        assert len(storm._inflight) == min(t + 1, storm.pipeline_depth)
        assert len(acks) == max(0, t + 1 - storm.pipeline_depth)
    storm.flush()  # drain
    assert storm._inflight == []
    assert sorted(a["rid"] for a in acks) == list(range(n_ticks))
    assert storm.stats["sequenced_ops"] == n_ticks * len(docs) * k
    for d in docs:
        assert (merge_host.map_entries(d, "default", "root")
                == replay_oracle(service, d))


def test_spill_log_restart_recovers_history(tmp_path):
    """A storm controller reopening a spill dir rebuilds its tick index:
    catch-up reads still materialize pre-restart ops, and fresh ticks
    never alias stale blobs (tick ids continue past the journal)."""
    import numpy as np

    from fluidframework_tpu.server.kernel_host import KernelSequencerHost
    from fluidframework_tpu.server.merge_host import KernelMergeHost
    from fluidframework_tpu.server.routerlicious import RouterliciousService
    from fluidframework_tpu.server.storm import StormController

    def build(spill):
        seq_host = KernelSequencerHost(num_slots=2, initial_capacity=4)
        merge_host = KernelMergeHost(row_capacity=4,
                                     flush_threshold=10**9)
        service = RouterliciousService(merge_host=merge_host,
                                       batched_deli_host=seq_host,
                                       auto_pump=False)
        storm = StormController(service, seq_host, merge_host,
                                flush_threshold_docs=1, spill_dir=spill)
        return service, storm

    spill = str(tmp_path / "spill")
    service, storm = build(spill)
    client = service.connect("doc", lambda msgs: None).client_id
    service.pump()
    words = np.arange(8, dtype=np.uint32) << 12
    storm.submit_frame(None, {"op": "storm",
                              "docs": [["doc", client, 1, 1, 8]]},
                       memoryview(words.tobytes()))
    storm.flush()
    before = service.get_deltas("doc", 0)
    assert sum(1 for m in before if m.type.name == "OPERATION") >= 8

    # "Restart": a fresh controller stack over the same spill dir. The
    # sequencer state is fresh, but the durable tick history must read
    # back, and new tick ids must continue past the journal.
    service2, storm2 = build(spill)
    assert storm2._tick_counter == storm._tick_counter
    recs = storm2.records_overlapping("doc", 0)
    assert recs and recs[0]["n_seq"] == 8
    words2 = np.asarray(
        np.frombuffer(storm2.read_tick_words(recs[0]["tick"]), np.uint32,
                      recs[0]["count"], recs[0]["w_off"]))
    assert (words2 == words).all()


def test_ingress_is_zero_copy_through_codec_and_submit():
    """THE zero-copy acceptance bar: the payload handed to submit_frame
    is parsed in place — the buffered frame's word view ALIASES the
    receive buffer (codec → submit_frame with no Python-level byte
    copy), and the only staging write is the tick scatter itself."""
    service, storm, merge_host = make_service()
    clients = join_docs(service, ["a", "b"])
    k = 16
    rng = np.random.default_rng(11)
    payload = b"".join(make_words(rng, k).tobytes() for _ in range(2))
    buf = bytearray(encode_storm_body(
        {"op": "storm", "rid": 1,
         "docs": [["a", clients["a"], 1, 1, k],
                  ["b", clients["b"], 1, 1, k]]}, payload))
    header, view = decode_storm_body(buf)
    assert view.obj is buf  # codec: memoryview-through
    storm.submit_frame(None, header, view)
    frame = storm._frames[0]
    base = np.frombuffer(buf, np.uint8)
    # submit_frame: ONE frombuffer view over the receive buffer — no
    # per-doc slicing copies, no re-parse.
    assert np.shares_memory(frame.words, base)
    storm.flush()
    assert storm.stats["sequenced_ops"] == 2 * k
    for d in ("a", "b"):
        assert merge_host.map_entries(d, "default", "root") \
            == replay_oracle(service, d)


def test_broadcast_fanout_is_batched_native_publishes():
    """O(batch) fan-out acceptance bar: one serving tick's broadcasts go
    through the fan-out service as ONE batched publish call (covering
    every doc), never one Python write per subscriber connection."""
    from fluidframework_tpu.native.fanout import make_fanout

    class CountingFanout:
        def __init__(self):
            self.inner = make_fanout(force_python=True)
            self.publish_calls = 0
            self.batch_calls = 0

        def publish(self, room, payload):
            self.publish_calls += 1
            return self.inner.publish(room, payload)

        def publish_batch(self, items):
            self.batch_calls += 1
            return self.inner.publish_batch(items)

        def __getattr__(self, name):
            return getattr(self.inner, name)

    from fluidframework_tpu.server.kernel_host import KernelSequencerHost
    from fluidframework_tpu.server.merge_host import KernelMergeHost

    fanout = CountingFanout()
    seq_host = KernelSequencerHost(num_slots=2, initial_capacity=8)
    merge_host = KernelMergeHost(flush_threshold=10**9)
    service = RouterliciousService(merge_host=merge_host,
                                   batched_deli_host=seq_host,
                                   auto_pump=False, fanout=fanout)
    storm = StormController(service, seq_host, merge_host,
                            flush_threshold_docs=10**9)
    docs = [f"d{i}" for i in range(6)]
    clients = join_docs(service, docs)
    # N read-only subscribers per doc on the fan-out rooms.
    subs = []
    for d in docs:
        for _ in range(4):
            sub = fanout.connect()
            fanout.join(sub, d)
            subs.append(sub)
    rng = np.random.default_rng(12)
    k = 8
    payload = b"".join(make_words(rng, k).tobytes() for _ in docs)
    fanout.batch_calls = fanout.publish_calls = 0
    storm.submit_frame(None, {
        "op": "storm", "docs": [[d, clients[d], 1, 1, k] for d in docs]},
        memoryview(payload))
    storm.flush()
    # ONE native batch call for the whole tick; zero per-room Python
    # publishes on the storm path.
    assert fanout.batch_calls == 1
    assert fanout.publish_calls == 0
    # ...and it really fanned out: every subscriber queue got its doc's
    # compact tick frame.
    for sub in subs:
        assert fanout.pending(sub) == 1
        assert fanout.poll(sub)[:1] == b"\x00"


def test_sequenced_broadcast_serialized_once_per_doc():
    """Satellite pin (delivered-bytes / encode-count): one sequenced op
    fanned to N subscriber sessions is JSON-encoded ONCE — every session
    pushes the SAME cached body bytes."""
    from fluidframework_tpu.protocol.codec import (
        BroadcastBatch,
        encode_ops_event,
        ops_event_encode_count,
    )
    from fluidframework_tpu.server.alfred import RequestSession

    class SinkSession(RequestSession):
        def __init__(self, server):
            super().__init__(server)
            self.sent = []

        def push(self, payload):
            self.sent.append(payload)

    service = RouterliciousService()
    server = type("S", (), {"service": service})()
    sessions = [SinkSession(server) for _ in range(5)]

    # The broadcaster hands EVERY subscriber the same BroadcastBatch
    # object (identity-shared per op delivery)...
    received = []
    for i in range(3):
        service.connect("doc", received.append)
    conn = service.connect("doc", received.append)
    received.clear()
    from fluidframework_tpu.protocol.messages import DocumentMessage
    conn.submit([DocumentMessage(
        client_sequence_number=1, reference_sequence_number=0,
        type=MessageType.OPERATION, contents={"k": 1})])
    assert received, "no broadcast delivered"
    batches = [b for b in received if isinstance(b, BroadcastBatch)]
    assert batches, "broadcast batches are not shared BroadcastBatch objects"
    first = batches[0]
    assert sum(1 for b in batches if b is first) >= 3  # same object, all subs

    # ...so the session push path encodes once however many sessions fan
    # it out, and each delivers the identical bytes.
    before = ops_event_encode_count()
    for s in sessions:
        s.push_ops(first)
    assert ops_event_encode_count() - before == 1
    bodies = [s.sent[0] for s in sessions]
    assert all(b is bodies[0] for b in bodies)
    delivered_bytes = sum(len(b) for b in bodies)
    assert delivered_bytes == len(bodies[0]) * len(sessions)
