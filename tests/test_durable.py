"""Durable storage: C++ op log, file bus/state store, git-style snapshots,
and full service recovery across a real process boundary.

Reference parity: Kafka segment recovery (services-ordering-*), Mongo
checkpoints (checkpointManager.ts:24), gitrest content-addressed snapshot
storage (gitrest/src/utils.ts:9) — a routerlicious pod restart resumes
from durable state; here the whole service dies with its process and a
fresh process rebuilds it from the same directory.
"""

from __future__ import annotations

import json
import subprocess
import sys
import textwrap

import pytest

from fluidframework_tpu.native import OpLog, _PythonOpLog, native_available
from fluidframework_tpu.server.durable_store import (
    DurableMessageBus,
    FileStateStore,
    GitSnapshotStore,
)


class TestOpLog:
    def test_native_toolchain_builds(self):
        assert native_available(), "g++ oplog build failed"

    def test_append_read_reopen(self, tmp_path):
        path = tmp_path / "a.log"
        log = OpLog(path)
        assert log.append(b"one") == 0
        assert log.append(b"two" * 1000) == 1
        log.sync()
        log.close()
        log = OpLog(path)
        assert len(log) == 2
        assert log.read(0) == b"one"
        assert log.read(1) == b"two" * 1000
        log.close()

    def test_torn_tail_truncates(self, tmp_path):
        path = tmp_path / "a.log"
        log = OpLog(path)
        log.append(b"good")
        log.close()
        with open(path, "ab") as f:  # simulate crash mid-append
            f.write(b"\x99\x00\x00\x00partial")
        log = OpLog(path)
        assert len(log) == 1 and log.read(0) == b"good"
        # And appends after recovery land cleanly.
        log.append(b"after")
        log.close()
        log = OpLog(path)
        assert [log.read(i) for i in range(len(log))] == [b"good", b"after"]
        log.close()

    def test_corrupt_crc_truncates(self, tmp_path):
        path = tmp_path / "a.log"
        log = OpLog(path)
        log.append(b"aaaa")
        log.append(b"bbbb")
        log.close()
        data = bytearray(path.read_bytes())
        data[-1] ^= 0xFF  # flip a byte in the last payload
        path.write_bytes(data)
        log = OpLog(path)
        assert len(log) == 1 and log.read(0) == b"aaaa"
        log.close()

    @pytest.mark.skipif(not native_available(), reason="no toolchain")
    def test_python_and_native_formats_interoperate(self, tmp_path):
        path = tmp_path / "x.log"
        py_log = _PythonOpLog(str(path))
        py_log.append(b"from-python")
        py_log.close()
        native = OpLog(path)
        assert native.read(0) == b"from-python"
        native.append(b"from-native")
        native.close()
        py_log = _PythonOpLog(str(path))
        assert [py_log.read(i) for i in range(2)] == [b"from-python",
                                                      b"from-native"]
        py_log.close()


class TestDurableBus:
    def test_produce_survives_reopen_with_offsets(self, tmp_path):
        bus = DurableMessageBus(tmp_path)
        bus.create_topic("t", 2)
        bus.produce("t", "doc-a", {"n": 1})
        bus.produce("t", "doc-a", {"n": 2})
        bus.commit("t", "g", 0, 1)
        bus.commit("t", "g", 1, 1)
        bus.close()

        bus = DurableMessageBus(tmp_path)
        topic = bus.create_topic("t", 2)
        msgs = [m for p in range(2) for m in topic.read(p, 0)]
        assert [m.value for m in msgs] == [{"n": 1}, {"n": 2}]
        parts = {m.offset for m in msgs}
        assert parts == {0, 1}
        committed = [bus.committed("t", "g", p) for p in range(2)]
        assert committed == [1, 1]

    def test_partition_count_pinned_at_creation(self, tmp_path):
        bus = DurableMessageBus(tmp_path)
        bus.create_topic("t", 8)
        for i in range(16):
            bus.produce("t", f"doc-{i}", i)
        bus.close()
        # Reopen asking for a different count: the recorded count wins, so
        # no partition log is orphaned and keys keep their partitions.
        bus = DurableMessageBus(tmp_path)
        topic = bus.create_topic("t", 4)
        assert topic.num_partitions == 8
        values = sorted(m.value for p in range(8) for m in topic.read(p, 0))
        assert values == list(range(16))

    def test_offset_log_compacts(self, tmp_path):
        bus = DurableMessageBus(tmp_path)
        bus.OFFSET_COMPACT_THRESHOLD = 8
        bus.create_topic("t", 1)
        for i in range(200):
            bus.commit("t", "g", 0, i + 1)
        assert len(bus._offset_log) < 50
        bus.close()
        bus = DurableMessageBus(tmp_path)
        assert bus.committed("t", "g", 0) == 200


class TestFileStateStore:
    def test_put_append_reopen_compact(self, tmp_path):
        store = FileStateStore(tmp_path)
        store.put("a", {"x": 1})
        store.append("log", [1, 2])
        store.append("log", [3])
        store.put("a", {"x": 2})
        store.close()

        store = FileStateStore(tmp_path)
        assert store.get("a") == {"x": 2}
        assert store.get("log") == [1, 2, 3]
        store.compact()
        store.close()
        store = FileStateStore(tmp_path)
        assert store.get("a") == {"x": 2}
        assert store.get("log") == [1, 2, 3]
        assert store.keys() == ["a", "log"]
        store.close()


class TestGitSnapshotStore:
    def test_upload_get_head_dedup(self, tmp_path):
        git = GitSnapshotStore(tmp_path)
        snap = {"sequence_number": 5, "tree": {"k": "v" * 100_000}}
        h1 = git.upload("doc", snap)
        h2 = git.upload("doc", snap)
        assert h1 == h2  # content-addressed dedup
        assert git.get("doc", h1) == snap
        assert git.head("doc") is None
        git.set_head("doc", h1)
        assert git.head("doc") == h1
        assert git.get("doc", "0" * 64) is None

    def test_traversal_handles_rejected(self, tmp_path):
        git = GitSnapshotStore(tmp_path)
        outside = tmp_path.parent / "secret.json"
        outside.write_text('{"chunks": []}')
        for evil in ("../secret.json", "../../etc/passwd", "a/b",
                     "A" * 64, "", None, 5):
            assert git.get("doc", evil) is None

    def test_state_store_auto_compacts(self, tmp_path):
        store = FileStateStore(tmp_path)
        store.COMPACT_THRESHOLD = 16
        for i in range(500):
            store.put("clock", i)
        assert len(store._journal) < 100
        store.close()
        store = FileStateStore(tmp_path)
        assert store.get("clock") == 499


_PHASE_A = textwrap.dedent("""
    import json, sys
    from fluidframework_tpu.dds.map import SharedMap
    from fluidframework_tpu.dds.sequence import SharedString
    from fluidframework_tpu.drivers.local_driver import LocalDocumentService
    from fluidframework_tpu.runtime.container import Container
    from fluidframework_tpu.server.alfred import build_default_service

    service = build_default_service(sys.argv[1], merge_host=False)
    c1 = Container.create_detached(LocalDocumentService(service, "doc"))
    ds = c1.runtime.create_datastore("default")
    ds.create_channel("root", SharedMap.channel_type)
    ds.create_channel("text", SharedString.channel_type)
    c1.attach()
    c2 = Container.load(LocalDocumentService(service, "doc"))

    t1 = c1.runtime.get_datastore("default").get_channel("text")
    t2 = c2.runtime.get_datastore("default").get_channel("text")
    m1 = c1.runtime.get_datastore("default").get_channel("root")
    t1.insert_text(0, "hello world")
    t2.insert_text(0, "crash: ")
    m1.set("alive", True)
    t1.remove_text(0, 1)

    print(json.dumps({"text": t1.get_text(),
                      "map": dict(m1.items())}), flush=True)
    # Die WITHOUT any shutdown/close — durability must not depend on it.
""")


class TestServiceRestartAcrossProcess:
    def test_recover_from_dead_process(self, tmp_path):
        proc = subprocess.run(
            [sys.executable, "-c", _PHASE_A, str(tmp_path)],
            capture_output=True, text=True, timeout=120)
        assert proc.returncode == 0, proc.stderr
        expected = json.loads(proc.stdout.strip().splitlines()[-1])

        # Fresh process (this one), fresh service object over the same dir.
        from fluidframework_tpu.drivers.local_driver import (
            LocalDocumentService)
        from fluidframework_tpu.runtime.container import Container
        from fluidframework_tpu.server.alfred import build_default_service

        service = build_default_service(str(tmp_path), merge_host=False)
        c3 = Container.load(LocalDocumentService(service, "doc"))
        text = c3.runtime.get_datastore("default").get_channel("text")
        root = c3.runtime.get_datastore("default").get_channel("root")
        assert text.get_text() == expected["text"]
        assert dict(root.items()) == expected["map"]

        # The recovered service still sequences: keep editing + a second
        # client converges.
        text.insert_text(0, "back! ")
        c4 = Container.load(LocalDocumentService(service, "doc"))
        text4 = c4.runtime.get_datastore("default").get_channel("text")
        assert text4.get_text() == "back! " + expected["text"]
        assert text.get_text() == text4.get_text()


class TestTornTailEveryOffset:
    """ISSUE 4 satellite: truncate the WAL at EVERY byte offset inside
    the final frame and prove recovery lands exactly on the last complete
    record — for the raw CRC framing and for _DurablePartition (the
    storm tick WAL gets the same sweep in test_storm_durability)."""

    def test_oplog_every_offset(self, tmp_path):
        path = tmp_path / "w.log"
        log = OpLog(path)
        log.append(b"first-record")
        log.append(b"second-record-" + b"x" * 40)
        log.close()
        full = path.read_bytes()
        first_frame_end = 8 + len(b"first-record")
        probe = tmp_path / "probe.log"
        for cut in range(first_frame_end, len(full)):
            probe.write_bytes(full[:cut])
            log = OpLog(probe)
            assert len(log) == 1, cut
            assert log.read(0) == b"first-record"
            # Appends after recovery land cleanly on the truncated tail.
            log.append(b"post")
            assert log.read(1) == b"post"
            log.close()

    def test_durable_partition_every_offset(self, tmp_path):
        from fluidframework_tpu.server.durable_store import _DurablePartition

        path = tmp_path / "t-0.log"
        part = _DurablePartition(path)
        part.append("doc-a", {"n": 1})
        part.append("doc-a", {"payload": "y" * 64})
        part.close()
        full = path.read_bytes()
        import struct
        (first_len,) = struct.unpack_from("<I", full, 0)
        first_frame_end = 8 + first_len
        probe = tmp_path / "probe-0.log"
        for cut in range(first_frame_end, len(full)):
            probe.write_bytes(full[:cut])
            part = _DurablePartition(probe)
            assert [m.value for m in part.log] == [{"n": 1}], cut
            part.close()


class TestGroupCommitLog:
    def test_watermark_callbacks_and_reopen(self, tmp_path):
        from fluidframework_tpu.server.durable_store import GroupCommitLog

        path = tmp_path / "g.log"
        log = GroupCommitLog(path)
        durable = []
        i0 = log.append(b"alpha", on_durable=durable.append)
        i1 = log.append([b"be", b"ta"], on_durable=durable.append)
        assert (i0, i1) == (0, 1)
        # Reads serve queued records without waiting for the fsync.
        assert log.read(1) == b"beta"
        log.sync()
        assert log.durable_len == 2
        assert sorted(durable) == [0, 1]
        log.close()
        log = GroupCommitLog(path)
        assert len(log) == 2 and log.durable_len == 2
        assert [log.read(i) for i in range(2)] == [b"alpha", b"beta"]
        log.close()

    def test_interoperates_with_plain_oplog(self, tmp_path):
        """The group writer and the sync OpLog share one file format —
        a durability-mode change (or rollback) never orphans a WAL."""
        from fluidframework_tpu.server.durable_store import GroupCommitLog

        path = tmp_path / "g.log"
        log = GroupCommitLog(path)
        log.append(b"from-group")
        log.sync()
        log.close()
        plain = OpLog(path)
        assert plain.read(0) == b"from-group"
        plain.append(b"from-plain")
        plain.close()
        log = GroupCommitLog(path)
        assert [log.read(i) for i in range(len(log))] \
            == [b"from-group", b"from-plain"]
        log.close()

    def test_commit_groups_partition_fsyncs(self, tmp_path):
        """Offsets never claim records the data log could lose: commit()
        syncs the dirty partition before journaling the offset."""
        bus = DurableMessageBus(tmp_path)
        bus.create_topic("t", 1)
        part = bus._topics["t"].partitions[0]
        for i in range(8):
            bus.produce("t", "doc", i)
        assert part.dirty  # appends buffered under one pending fsync
        bus.commit("t", "g", 0, 8)
        assert not part.dirty  # the commit group-synced them
        bus.close()


# -- the write path keeps values already in replay shape ----------------------


def _same_tree(a, b):
    """Equal, with the same type at every node (NaN equals NaN)."""
    import dataclasses
    import math

    assert type(a) is type(b), (a, b)
    if type(a) is float and math.isnan(a):
        assert math.isnan(b)
    elif type(a) is dict:
        assert list(a) == list(b)
        for k in a:
            _same_tree(a[k], b[k])
    elif type(a) in (list, tuple):
        assert len(a) == len(b)
        for x, y in zip(a, b):
            _same_tree(x, y)
    elif dataclasses.is_dataclass(a):
        for f in dataclasses.fields(a):
            _same_tree(getattr(a, f.name), getattr(b, f.name))
    else:
        assert a == b


def _replay_cases():
    from enum import IntEnum

    from fluidframework_tpu.protocol.messages import (
        ClientDetail, DocumentMessage, MessageType, NackErrorType,
        NackMessage, SequencedDocumentMessage, Trace)
    from fluidframework_tpu.server.sequencer import RawOperation

    class Color(IntEnum):
        RED = 1

    traces = (Trace("alfred", "submit", 1.5), Trace("deli", "start", 2.0))

    def raw(contents):
        return RawOperation(client_id="c1", type=MessageType.OPERATION,
                            client_seq=3, ref_seq=2, timestamp=7,
                            contents=contents, traces=traces)

    def seq(contents):
        return SequencedDocumentMessage(
            client_id="c1", sequence_number=9, minimum_sequence_number=4,
            client_sequence_number=3, reference_sequence_number=2,
            type=MessageType.OPERATION, contents=contents, traces=traces,
            timestamp=7)

    checkpoint = {
        "sequence_number": 9, "minimum_sequence_number": 4,
        "last_sent_msn": 4, "no_active_clients": False,
        "clients": [{"client_id": "c1", "client_seq": 3, "ref_seq": 2,
                     "last_update": 7, "can_evict": True,
                     "can_summarize": True, "nack": False}],
        "nack_future": False, "client_timeout_ms": 300000,
        "log_offset": 12, "summary_responded": 0}
    # (name, value, whether the write path must decode its bytes)
    return [
        ("plain_dict", {"a": 1, "b": [1.5, "x", None, True],
                        "c": {"d": -0.0}}, False),
        ("int_key", {1: "a", "b": 2}, True),
        ("contents_tuple", raw({"pos": (1, 2)}), True),
        ("contents_intenum", seq({"color": Color.RED}), True),
        ("contents_tag_key", DocumentMessage(
            client_sequence_number=1, reference_sequence_number=0,
            type=MessageType.OPERATION,
            contents={"_t": "trace", "service": "s", "action": "a",
                      "timestamp": 1.0}), True),
        ("contents_nan", raw({"value": float("nan")}), True),
        ("raw_with_traces", raw({"address": "grid", "contents": {
            "type": "setCell", "row": 1, "col": 2, "value": 3}}), False),
        ("seq_with_traces", seq({"type": "setCell", "value": [1, 2]}),
         False),
        ("join", RawOperation(client_id=None, type=MessageType.CLIENT_JOIN,
                              data=ClientDetail(client_id="c1",
                                                scopes=("doc:read",)),
                              timestamp=5), False),
        ("nack", NackMessage(
            operation=DocumentMessage(client_sequence_number=1,
                                      reference_sequence_number=0,
                                      type=MessageType.OPERATION,
                                      contents={"k": "v"}),
            sequence_number=9, code=400,
            error_type=NackErrorType.BAD_REQUEST, message="nack:1"), False),
        ("deli_checkpoint", checkpoint, False),
        ("deli_checkpoint_tuple_clients",
         {**checkpoint, "clients": [("c1", 3, 2)]}, True),
    ]


_CASES = _replay_cases()


class TestReplayShape:
    @pytest.mark.parametrize("path", ["produce", "put", "append"])
    @pytest.mark.parametrize("name,value,decodes", _CASES,
                             ids=[c[0] for c in _CASES])
    def test_memory_matches_replay(self, tmp_path, path, name, value,
                                   decodes):
        """Through each write path: the in-memory copy equals what a
        reopen replays, type for type; the bytes are ``_dump``'s; and the
        decode runs exactly for values not already in replay shape."""
        from fluidframework_tpu.server.durable_store import _dump

        if path == "produce":
            bus = DurableMessageBus(tmp_path)
            bus.create_topic("t", 1)
            bus.produce("t", "doc", value)
            held = bus.topic("t").read(0, 0)[0].value
            counters = bus.metrics.snapshot()
            bus.close()
            bus = DurableMessageBus(tmp_path)
            bus.create_topic("t", 1)
            replayed = bus.topic("t").read(0, 0)[0].value
            bus.close()
            log, record = OpLog(tmp_path / "t-0.log"), ["doc", value]
        else:
            store = FileStateStore(tmp_path)
            if path == "put":
                store.put("k", value)
                held = store.get("k")
                record = ["put", "k", value]
            else:
                store.append("k", [value])
                held = store.get("k")[0]
                record = ["append", "k", [value]]
            counters = store.metrics.snapshot()
            store.close()
            store = FileStateStore(tmp_path)
            replayed = store.get("k") if path == "put" \
                else store.get("k")[0]
            store.close()
            log = OpLog(tmp_path / "state.log")
        _same_tree(held, replayed)
        assert len(log) == 1 and log.read(0) == _dump(record)
        log.close()
        assert counters["durable.records"] == 1
        assert counters["durable.replay_decodes"] == int(decodes)
        if not decodes and path == "put":
            assert held is value  # kept as given, not rebuilt


def _durable_service(tmp_path):
    from fluidframework_tpu.server.routerlicious import RouterliciousService

    return RouterliciousService(
        bus=DurableMessageBus(tmp_path / "bus"),
        store=FileStateStore(tmp_path / "state"), auto_pump=False)


def _set_cell(cseq, contents_value):
    from fluidframework_tpu.protocol.messages import (DocumentMessage,
                                                      MessageType)

    return DocumentMessage(
        client_sequence_number=cseq, reference_sequence_number=1,
        type=MessageType.OPERATION,
        contents={"address": "grid", "contents": {
            "type": "setCell", "row": cseq, "col": 2,
            "value": contents_value}})


class TestDurableWritePath:
    def test_submitted_contents_stay_the_callers(self, tmp_path):
        """The log's copy of a submitted op shares no container with the
        caller's DocumentMessage: mutating it after submit changes
        neither the RAWDELTAS message nor, after a pump, rawops/ or ops/."""
        import copy

        from fluidframework_tpu.server.orderer import RAWDELTAS

        service = _durable_service(tmp_path)
        conn = service.connect("doc", lambda ops: None)
        service.pump()
        message = _set_cell(1, [1, 2])
        before = copy.deepcopy(message.contents)
        conn.submit([message])
        message.contents["contents"]["value"].append(3)
        message.contents["contents"]["row"] = 99
        message.contents["extra"] = True

        topic = service.bus.topic(RAWDELTAS)
        raws = [m.value for p in range(topic.num_partitions)
                for m in topic.read(p, 0) if m.key == "doc"]
        assert raws[-1].contents == before
        service.pump()
        assert service.store.get("rawops/doc")[-1].contents == before
        assert service.store.get("ops/doc")[-1].contents == before
        service.bus.close()
        service.store.close()

    def test_records_and_decodes_counted(self, tmp_path):
        """Five records an op (clock, RAWDELTAS, DELTAS, rawops/, ops/)
        plus the lambdas' checkpoints. Only the RAWDELTAS record of the op
        whose contents hold a tuple decodes: every later record carries
        the bus's decoded copy, in replay shape."""
        from fluidframework_tpu.server.durable_store import _load

        service = _durable_service(tmp_path)
        conns = [service.connect(doc, lambda ops: None)
                 for doc in ("doc-a", "doc-b")]
        service.pump()
        journal = service.store._journal
        start = len(journal)
        base = service.metrics.snapshot()
        n = 0
        for conn in conns:
            for cseq in range(1, 7):
                conn.submit([_set_cell(cseq, cseq * 10)])
                n += 1
        conns[0].submit([_set_cell(7, (1, 2))])  # a tuple: decodes
        n += 1
        service.pump()
        snap = service.metrics.snapshot()
        keys = [_load(journal.read(i))[1] for i in range(start, len(journal))]
        per_op = [k for k in keys
                  if k == "clock" or k.startswith(("ops/", "rawops/"))]
        assert len(per_op) == 3 * n
        checkpoints = len(keys) - len(per_op)
        assert checkpoints > 0
        records = snap["durable.records"] - base["durable.records"]
        assert records == 5 * n + checkpoints
        decodes = (snap["durable.replay_decodes"]
                   - base.get("durable.replay_decodes", 0))
        assert decodes == 1
        assert service.store.get("ops/doc-a")[-1].contents[
            "contents"]["value"] == [1, 2]
        service.bus.close()
        service.store.close()


def _backend(name):
    from fluidframework_tpu.native import _NativeOpLog

    if name == "native":
        return lambda path: _NativeOpLog(str(path))
    return lambda path: _PythonOpLog(str(path))


_BACKENDS = [
    pytest.param("native", marks=pytest.mark.skipif(
        not native_available(), reason="no toolchain")),
    "python",
]
_PAYLOADS = [b"", b"a", b"bb" * 70, b"\x00\xff" * 9, b"z" * 4096, b"end"]


@pytest.mark.parametrize("backend", _BACKENDS)
class TestAppendMany:
    """``OpLog.append_many``: one write of many records, framed as
    ``append`` frames each; both backends."""

    def test_bytes_match_appends_one_by_one(self, tmp_path, backend):
        make = _backend(backend)
        one, many = make(tmp_path / "one.log"), make(tmp_path / "many.log")
        for p in _PAYLOADS:
            one.append(p)
        assert many.append_many(_PAYLOADS) == 0
        one.close()
        many.close()
        assert (tmp_path / "many.log").read_bytes() \
            == (tmp_path / "one.log").read_bytes()
        log = make(tmp_path / "many.log")
        assert [log.read(i) for i in range(len(log))] == _PAYLOADS
        log.close()

    def test_indices_continue_across_mixed_appends(self, tmp_path,
                                                   backend):
        make = _backend(backend)
        log = make(tmp_path / "m.log")
        assert log.append(b"first") == 0
        assert log.append_many([b"b1", b"b2", b"b3"]) == 1
        assert log.append(b"mid") == 4
        assert log.append_many([b"c1", b"c2"]) == 5
        assert len(log) == 7 and log.read(3) == b"b3" \
            and log.read(6) == b"c2"
        log.close()
        log = make(tmp_path / "m.log")
        assert [log.read(i) for i in range(len(log))] == [
            b"first", b"b1", b"b2", b"b3", b"mid", b"c1", b"c2"]
        assert log.append_many([b"d"]) == 7
        log.close()

    def test_cut_inside_a_batch_reopens_to_last_whole_record(
            self, tmp_path, backend):
        make = _backend(backend)
        path = tmp_path / "w.log"
        log = make(path)
        log.append(b"before")
        batch = [b"r%d-" % i + b"x" * i for i in range(6)]
        log.append_many(batch)
        log.close()
        full = path.read_bytes()
        ends, pos = [], 8 + len(b"before")
        for p in batch:
            pos += 8 + len(p)
            ends.append(pos)
        probe = tmp_path / "probe.log"
        for cut in range(8 + len(b"before"), len(full)):
            probe.write_bytes(full[:cut])
            log = make(probe)
            whole = 1 + sum(end <= cut for end in ends)
            assert len(log) == whole, cut
            assert [log.read(i) for i in range(whole)] \
                == [b"before", *batch[:whole - 1]]
            log.close()
            assert probe.stat().st_size == 8 + len(b"before") + sum(
                8 + len(p) for p in batch[:whole - 1])

    def test_empty_batch_writes_nothing(self, tmp_path, backend):
        make = _backend(backend)
        path = tmp_path / "e.log"
        log = make(path)
        assert log.append_many([]) == 0
        log.append(b"one")
        size = path.stat().st_size
        assert log.append_many([]) == 1
        assert path.stat().st_size == size and len(log) == 1
        log.close()


class TestStagedWrites:
    def test_files_hold_every_record_at_each_commit_and_sync(
            self, tmp_path):
        """After each offset record and each store fsync, a second bus
        and store opened on the same directories read exactly the first
        one's records and offsets: no offset or checkpoint ever runs
        ahead of a record on its file. A round of 1,024 ops writes each
        file's staged records in far fewer writes than records."""
        service = _durable_service(tmp_path)
        bus, store = service.bus, service.store
        checks = []

        def check(point):
            # A second bus and store over the same directories, the first
            # left open: what a process that died here would recover.
            second_bus = DurableMessageBus(tmp_path / "bus")
            second_store = FileStateStore(tmp_path / "state")
            for name, topic in bus._topics.items():
                theirs = second_bus.create_topic(name)
                for p, part in enumerate(topic.partitions):
                    assert part.base == 0
                    got = [(m.offset, m.key, m.value)
                           for m in theirs.partitions[p].log]
                    want = [(m.offset, m.key, m.value) for m in part.log]
                    assert got == want, (point, name, p)
            assert second_bus._offsets == bus._offsets, point
            assert second_store.keys() == store.keys(), point
            for key in store.keys():
                assert second_store.get(key) == store.get(key), (point, key)
            second_bus.close()
            second_store.close()
            checks.append(point)

        commit, sync = bus.commit, store.sync

        def checked_commit(*args):
            commit(*args)
            check(("commit", *args))

        def checked_sync():
            sync()
            check(("sync",))

        bus.commit, store.sync = checked_commit, checked_sync
        docs = [f"doc-{i}" for i in range(4)]
        conns = {d: [service.connect(d, lambda ops: None) for _ in range(4)]
                 for d in docs}
        service.pump()
        cseq = 0
        for ops_per_conn in (1, 3, 64):
            base = service.metrics.snapshot()
            for writers in conns.values():
                for conn in writers:
                    conn.submit([_set_cell(cseq + i + 1, i)
                                 for i in range(ops_per_conn)])
            cseq += ops_per_conn
            service.pump()
            snap = service.metrics.snapshot()
        ops = 16 * ops_per_conn
        assert ops >= 1024
        records = snap["durable.records"] - base["durable.records"]
        writes = snap["durable.writes"] - base.get("durable.writes", 0)
        assert records >= 5 * ops and writes > 0
        assert records / writes >= 50, (records, writes)
        assert len(service.store.get("ops/doc-0")) >= 4 * 68
        assert sum(c[0] == "commit" for c in checks) > 0
        assert sum(c[0] == "sync" for c in checks) > 0
        bus.close()
        store.close()

    def test_staged_bytes_past_the_limit_reach_the_file(self, tmp_path,
                                                       monkeypatch):
        """Past ``LIMIT_BYTES`` a stage writes what it holds before it
        takes the next record; unsynced records after that stay staged
        until the store's sync."""
        from fluidframework_tpu.server.durable_store import _WriteStage

        monkeypatch.setattr(_WriteStage, "LIMIT_BYTES", 1000)
        store = FileStateStore(tmp_path / "state")
        items = [{"i": i, "pad": "x" * 40} for i in range(40)]
        for item in items:
            store.append("ops/doc", [item])

        def on_file():
            second = FileStateStore(tmp_path / "state")
            got = second.get("ops/doc", [])
            second.close()
            return got

        got = on_file()
        writes = store.metrics.snapshot()["durable.writes"]
        assert writes >= 2 and 0 < len(got) < len(items)
        assert got == items[:len(got)]
        store.sync()
        assert on_file() == items
        store.close()
