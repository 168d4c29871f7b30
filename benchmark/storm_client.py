"""Storm-frame load client of the benchmark: one TCP connection that sends
map op-storm frames to the serving front door and times the acks.

Copied from ``fluidframework_tpu/tools/storm_client.py`` (PR 21) so that a
later PR's change to the tool cannot move the yardstick, and extended with
a timed window, an open-loop schedule and the generator's lateness.

Run as a plain script: it imports neither the program nor JAX (the serving
process owns the chip). Protocol on stdin/stdout:

1. one JSON config line in; the client builds its frame bodies and prints
   ``READY``;
2. ``GO <t0> <t_end>`` in, both on the system-wide monotonic clock
   (``time.monotonic``); the client sends from ``t0`` until ``t_end``;
3. one JSON result line out, once every frame it sent is answered (or the
   drain deadline, 60 s past ``t_end``, has passed).

Config: ``port``, ``seed`` (list of ints: the generator's seed sequence),
``k`` (ops per doc per frame), ``num_slots``, ``kinds`` (op-kind draw:
0 set, 1 delete, 2 clear), ``bodies`` (distinct word bodies; frame ``rid``
carries body ``rid % bodies``), ``docs`` ([[doc_id, [client_id, ...]],
...]; frame ``rid`` writes every doc through client ``rid % len``),
``cseq0`` (per doc, per client first clientSequenceNumber), ``loop``
(``closed`` with ``window`` frames in flight, or ``open`` with
``frames_per_s`` and ``phase_s``).

Result: per sent frame ``[rid, due, sent, acked]`` (monotonic seconds,
``acked`` null when no ack came, or a nack), the acked rids in ack order.
"""

import json
import socket
import struct
import sys
import threading
import time

import numpy as np

DRAIN_S = 60.0


def make_bodies(seed, n_docs, k, num_slots, kinds, bodies):
    """The frame word bodies, ``bodies`` arrays u32[n_docs, k]: word =
    kind | slot << 2 | value << 12. The benchmark's reference rebuilds
    them with this same function. ``seed`` is a list of whole numbers of
    any sign and size."""
    rng = np.random.default_rng([x % (1 << 63) for x in seed])
    out = []
    for _ in range(bodies):
        kind = rng.choice(np.asarray(kinds, np.uint32), size=(n_docs, k))
        slot = rng.integers(0, num_slots, (n_docs, k)).astype(np.uint32)
        val = rng.integers(0, 1 << 20, (n_docs, k)).astype(np.uint32)
        out.append(kind | (slot << 2) | (val << 12))
    return out


def main() -> None:
    cfg = json.loads(sys.stdin.readline())
    docs = cfg["docs"]
    k = cfg["k"]
    bodies = [b.tobytes() for b in make_bodies(
        cfg["seed"], len(docs), k, cfg["num_slots"], cfg["kinds"],
        cfg["bodies"])]
    cseqs = [list(c0) for c0 in cfg["cseq0"]]
    sock = socket.create_connection(("127.0.0.1", cfg["port"]))
    sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)

    def frame(rid):
        hdr_docs = []
        for i, (doc_id, clients) in enumerate(docs):
            c = rid % len(clients)
            hdr_docs.append([doc_id, clients[c], cseqs[i][c], 1, k])
            cseqs[i][c] += k
        head = json.dumps({"op": "storm", "rid": rid, "docs": hdr_docs},
                          separators=(",", ":")).encode()
        body = (bytes((0, 1)) + struct.pack("<I", len(head)) + head
                + bodies[rid % len(bodies)])
        return struct.pack(">I", len(body)) + body

    def recv_exact(n):
        raw = bytearray()
        while len(raw) < n:
            chunk = sock.recv(n - len(raw))
            if not chunk:
                raise EOFError("server closed the connection")
            raw += chunk
        return bytes(raw)

    def read_ack():
        length = struct.unpack(">I", recv_exact(4))[0]
        body = recv_exact(length)
        if body[:1] == b"\x00":
            # Binary columnar storm ack: header JSON + i32 rows; the
            # client needs only the header.
            hlen = struct.unpack_from("<I", body, 2)[0]
            hdr = json.loads(body[6:6 + hlen].decode())
            if hdr.get("op") == "storm_ack":
                hdr["storm"] = True
            return hdr
        return json.loads(body.decode())

    print("READY", flush=True)
    go = sys.stdin.readline().split()
    assert go[0] == "GO", go
    t0, t_end = float(go[1]), float(go[2])

    frames = {}          # rid -> [rid, due, sent, acked]
    order = []           # acked rids, in ack order
    lock = threading.Condition()
    inflight = [0]
    sending_done = threading.Event()

    def reader():
        try:
            while True:
                with lock:
                    if sending_done.is_set() and inflight[0] == 0:
                        return
                ack = read_ack()
                now = time.monotonic()
                if not ack.get("storm"):
                    continue
                rid = int(ack["rid"])
                with lock:
                    inflight[0] -= 1
                    if not ack.get("error"):
                        frames[rid][3] = now
                        order.append(rid)
                    lock.notify_all()
        except (EOFError, OSError):
            with lock:
                inflight[0] = -1
                lock.notify_all()

    while time.monotonic() < t0:
        time.sleep(min(0.01, max(0.0, t0 - time.monotonic())))
    rd = threading.Thread(target=reader, daemon=True)
    rd.start()
    rid = 0
    if cfg["loop"] == "open":
        period = 1.0 / float(cfg["frames_per_s"])
        due = t0 + float(cfg.get("phase_s", 0.0))
        while due < t_end:
            data = frame(rid)  # built before its due time
            now = time.monotonic()
            if now < due:
                time.sleep(due - now)
            sent = time.monotonic()
            with lock:
                frames[rid] = [rid, due, sent, None]
                inflight[0] += 1
            sock.sendall(data)
            rid += 1
            due += period
    else:
        window = int(cfg["window"])
        while True:
            data = frame(rid)
            with lock:
                while 0 <= inflight[0] >= window \
                        and time.monotonic() < t_end:
                    lock.wait(timeout=max(0.0, t_end - time.monotonic()))
                now = time.monotonic()
                if now >= t_end or inflight[0] < 0:
                    break
                frames[rid] = [rid, now, now, None]
                inflight[0] += 1
            sock.sendall(data)
            rid += 1
    with lock:
        sending_done.set()
        deadline = t_end + DRAIN_S
        while inflight[0] > 0 and time.monotonic() < deadline:
            lock.wait(timeout=0.5)
    sock.close()
    print(json.dumps({"frames": [frames[r] for r in sorted(frames)],
                      "order": order, "k": k, "n_docs": len(docs)}),
          flush=True)


if __name__ == "__main__":
    main()
