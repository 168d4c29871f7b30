"""The per-op path in batched cadence: RouterliciousService with
auto_pump=False, the device deli and the merger's KernelMergeHost serving
SharedMatrix writers — in process at 260 writers, and through the bridge
front door's rounds with the ``perop.stage`` ledger."""

from __future__ import annotations

import json
import random
import socket
import struct
import time

import pytest

from benchmark import ref_matrix
from fluidframework_tpu.native.bridge import _load_library
from fluidframework_tpu.protocol.messages import DocumentMessage, MessageType
from fluidframework_tpu.server.kernel_host import KernelSequencerHost
from fluidframework_tpu.server.merge_host import KernelMergeHost
from fluidframework_tpu.server.routerlicious import RouterliciousService
from fluidframework_tpu.utils.metrics import PEROP_STAGES


def _service(writers: int) -> RouterliciousService:
    return RouterliciousService(
        merge_host=KernelMergeHost(flush_threshold=10**9,
                                   matrix_vec_slots=256, matrix_handles=64,
                                   matrix_writers=writers + 1),
        batched_deli_host=KernelSequencerHost(num_slots=512,
                                              initial_capacity=2),
        auto_pump=False)


def _op(cseq: int, ref: int, channel_op: dict) -> DocumentMessage:
    return DocumentMessage(
        client_sequence_number=cseq, reference_sequence_number=ref,
        type=MessageType.OPERATION,
        contents={"address": "default", "contents": {
            "address": "grid", "contents": channel_op}})


def _ref_ops(service, doc):
    return [(m.sequence_number, m.reference_sequence_number, m.client_id,
             m.contents["contents"]["contents"])
            for m in service.get_deltas(doc, 0)
            if m.type == MessageType.OPERATION]


def _counter(service, name):
    return service.metrics.counter(name).value


@pytest.mark.parametrize("seed", range(2))
def test_batched_cadence_serves_260_writers_on_the_device(seed):
    """One pump a round: one device deli tick over every op, one merge
    flush; 260 writers (nine overlap words) stay on the device path and
    the grid matches the plain reference's replay of the durable log."""
    rng = random.Random(seed)
    service = _service(260)
    setup = service.connect("doc", lambda _m: None)
    service.pump()
    head = service.get_deltas("doc", 0)[-1].sequence_number
    setup.submit([_op(1, head, {"target": "rows", "type": "insert",
                                "pos": 0, "count": 12}),
                  _op(2, head, {"target": "cols", "type": "insert",
                                "pos": 0, "count": 12})])
    service.pump()
    writers = [service.connect("doc", lambda _m: None) for _ in range(260)]
    service.pump()
    rows = cols = 12
    for rnd in range(3):
        head = service.get_deltas("doc", 0)[-1].sequence_number
        ticks = _counter(service, "deli.device_ticks")
        for w, conn in enumerate(writers):
            if w % 40 == rnd:  # a structural op of this writer's view
                op = {"target": "rows", "type": "insert",
                      "pos": rng.randrange(rows + 1), "count": 2}
                rows += 2
            else:
                op = {"target": "cell", "type": "set",
                      "row": rng.randrange(rows - 2 * (rnd < 1)),
                      "col": rng.randrange(cols), "value": w + 1000 * rnd}
            conn.submit([_op(rnd + 1, head, op)])
        service.pump()
        assert _counter(service, "deli.device_ticks") == ticks + 1
    grid = service.merge_host.matrix_grid("doc", "default", "grid")
    assert grid == ref_matrix.replay(_ref_ops(service, "doc"))
    assert _counter(service, "matrix.ops_scalar") == 0
    assert _counter(service, "matrix.ops_device") >= 3 * 260
    assert service.merge_host.matrix_path("doc", "default", "grid") \
        == "device"


def _frame(payload: dict) -> bytes:
    body = json.dumps(payload).encode()
    return struct.pack(">I", len(body)) + body


def _read(sock) -> dict:
    def exact(n):
        buf = b""
        while len(buf) < n:
            chunk = sock.recv(n - len(buf))
            assert chunk, "server closed"
            buf += chunk
        return buf
    return json.loads(exact(struct.unpack(">I", exact(4))[0]))


@pytest.mark.skipif(_load_library() is None, reason="no C++ toolchain")
def test_bridge_rounds_pump_once_and_the_ledger_closes():
    """The bridge front door over a batched-cadence service: one
    ``service.pump()`` a poll pass, at most one deli tick a round, one
    ``perop.stage`` record a round whose stages sum to at most its wall,
    and a refused request's decode time refunded."""
    from fluidframework_tpu.server.bridge_host import BridgeFrontDoor

    service = _service(8)
    setup = service.connect("doc", lambda _m: None)
    service.pump()
    head = service.get_deltas("doc", 0)[-1].sequence_number
    setup.submit([_op(1, head, {"target": "rows", "type": "insert",
                                "pos": 0, "count": 4}),
                  _op(2, head, {"target": "cols", "type": "insert",
                                "pos": 0, "count": 4})])
    service.pump()
    pumps = []
    pump = service.pump

    def counted():
        ticks = _counter(service, "deli.device_ticks")
        pump()
        pumps.append(_counter(service, "deli.device_ticks") - ticks)

    service.pump = counted
    front = BridgeFrontDoor(service, 0)
    socks = []
    try:
        ids = []
        for _ in range(6):
            sock = socket.create_connection(("127.0.0.1", front.port))
            sock.sendall(_frame({"rid": 0, "op": "connect", "doc_id": "doc",
                                 "mode": "write"}))
            socks.append(sock)
        for sock in socks:
            msg = _read(sock)
            while "client_id" not in msg:
                msg = _read(sock)
            ids.append(msg["client_id"])
        head = service.get_deltas("doc", 0)[-1].sequence_number
        for i, sock in enumerate(socks):
            sock.sendall(_frame({"rid": 1, "op": "submit", "messages": [{
                "_t": "doc", "client_sequence_number": 1,
                "reference_sequence_number": head, "type": 8,
                "contents": {"address": "default", "contents": {
                    "address": "grid", "contents": {
                        "target": "cell", "type": "set", "row": i % 4,
                        "col": 1, "value": i}}},
                "metadata": None, "server_metadata": None,
                "traces": []}]}))
        # Each writer sees its op sequenced in an ops event.
        for sock, cid in zip(socks, ids):
            seen = False
            while not seen:
                msg = _read(sock)
                seen = msg.get("event") == "ops" and any(
                    m["client_id"] == cid and m["type"] == 8
                    for m in msg["messages"])
        ledger = service.ledger
        deadline = time.monotonic() + 10
        while len(ledger) < len(pumps) or not pumps:
            assert time.monotonic() < deadline
            time.sleep(0.01)  # the round that pushed the events records
        before = ledger.pending()["ingress_decode"]
        session = next(iter(front._sessions))
        front._handle_data(session, b"\xff not json")
        assert ledger.pending()["ingress_decode"] == before
    finally:
        for sock in socks:
            sock.close()
        front.close()
    records = ledger.records()
    assert records and len(records) == len(pumps)
    assert max(pumps) == 1  # a round ticks the deli once, or not at all
    for rec in records:
        assert sum(rec[s] for s in PEROP_STAGES) <= rec["wall"]
    assert sum(r["batch_ops"] for r in records) == 6
    assert service.metrics.counter("broadcast.frames_pushed").value > 0
    grid = service.merge_host.matrix_grid("doc", "default", "grid")
    assert grid == ref_matrix.replay(_ref_ops(service, "doc"))
