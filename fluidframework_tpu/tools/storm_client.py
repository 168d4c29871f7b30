"""Storm-frame load client: one TCP connection that sends pre-built map
op-storm frames to a serving front door and reads the acks.

Run as a plain script (``python fluidframework_tpu/tools/storm_client.py``)
so that it imports neither this package nor JAX: the serving process owns
the chip, and a child that touched JAX would contend for it. The config
arrives as one JSON line on stdin; the client prints ``READY`` once its
frames are built, waits for ``GO``, and prints one JSON result line.

Config: ``port``, ``seed``, ``k`` (ops per doc per frame), ``ticks``,
``num_slots`` (key slots), ``docs`` ([[doc_id, [client_id, ...]], ...];
frame ``t`` writes each doc through client ``t % len(clients)``),
``cseq0`` (per doc, per client first clientSequenceNumber), ``kinds``
(op-kind draw: 0 set, 1 delete, 2 clear), ``window`` (frames in flight;
<= 0 sends all), ``trace_every`` (every n-th frame carries a trace id).
"""

import json
import socket
import struct
import sys
import time

import numpy as np


def main() -> None:
    cfg = json.loads(sys.stdin.readline())
    sock = socket.create_connection(("127.0.0.1", cfg["port"]))
    rng = np.random.default_rng(cfg["seed"])
    docs = cfg["docs"]
    k = cfg["k"]
    kinds_draw = cfg.get("kinds", [0, 0, 0, 1, 2])
    trace_every = cfg.get("trace_every", 0)
    cseqs = [list(c0) for c0 in cfg["cseq0"]]

    def frame(rid):
        # (bytes, tc): every trace_every-th frame carries a sampled trace
        # id ("tc" header field); the server timestamps it at every hop
        # and the traced ack carries the joined marks back.
        hdr_docs, chunks = [], []
        for i, (doc_id, clients) in enumerate(docs):
            c = rid % len(clients)
            kinds = rng.choice(kinds_draw, size=k).astype(np.uint32)
            slots = rng.integers(0, cfg["num_slots"], k).astype(np.uint32)
            vals = rng.integers(0, 1 << 20, k).astype(np.uint32)
            chunks.append(kinds | (slots << 2) | (vals << 12))
            hdr_docs.append([doc_id, clients[c], cseqs[i][c], 1, k])
            cseqs[i][c] += k
        header = {"op": "storm", "rid": rid, "docs": hdr_docs}
        tc = None
        if trace_every and rid % trace_every == 0:
            tc = cfg["seed"] * 1_000_000 + rid
            header["tc"] = tc
        head = json.dumps(header, separators=(",", ":")).encode()
        body = (bytes((0, 1)) + struct.pack("<I", len(head)) + head
                + b"".join(c.tobytes() for c in chunks))
        return struct.pack(">I", len(body)) + body, tc

    def recv_exact(n):
        raw = b""
        while len(raw) < n:
            chunk = sock.recv(n - len(raw))
            if not chunk:
                raise SystemExit("server closed the connection")
            raw += chunk
        return raw

    def read_ack():
        length = struct.unpack(">I", recv_exact(4))[0]
        body = recv_exact(length)
        if body[:1] == b"\x00":
            # Binary columnar storm ack: header JSON + i32[n,4] rows. The
            # client only needs the header.
            hlen = struct.unpack_from("<I", body, 2)[0]
            hdr = json.loads(body[6:6 + hlen].decode())
            if hdr.get("op") == "storm_ack":
                hdr["storm"] = True
            return hdr
        return json.loads(body.decode())

    frames = [frame(t) for t in range(cfg["ticks"])]  # pre-built, untimed
    window = cfg.get("window", 0)
    print("READY", flush=True)
    assert sys.stdin.readline().strip() == "GO"
    t0 = time.perf_counter()
    send_ns = {}
    ack_times, acked, hop_rows, nacked = [], 0, [], 0
    # Windowed flow control: at most `window` frames in flight, keyed off
    # the ack stream — measured ack latency is then SERVER latency, not
    # the client's own send backlog. A busy-nack frees its window slot
    # but the frame resends after the hint — it was never sequenced, so
    # it never counts toward the acked total.
    to_send = list(range(cfg["ticks"]))
    inflight = 0
    while acked < cfg["ticks"]:
        if to_send and (window <= 0 or inflight < window):
            data, tc = frames[to_send.pop(0)]
            if tc is not None:
                send_ns[tc] = time.monotonic_ns()  # server hops share clock
            sock.sendall(data)
            inflight += 1
            continue
        ack = read_ack()
        rx_ns = time.monotonic_ns()
        if not ack.get("storm"):
            continue
        inflight -= 1
        if ack.get("error"):
            nacked += 1
            time.sleep(float(ack.get("retry_after_s", 0.01)))
            to_send.append(int(ack["rid"]))
            continue
        acked += 1
        ack_times.append(time.perf_counter() - t0)
        tc, hops = ack.get("tc"), ack.get("hops")
        if tc in send_ns and hops:
            # End-to-end join: client send -> server hop marks -> client
            # rx, one monotonic clock domain (same host), ms per hop.
            marks = ([("client_send", send_ns.pop(tc))]
                     + list(hops.items()) + [("client_rx", rx_ns)])
            hop_rows.append({"%s_to_%s" % (a, b): (tb - ta) / 1e6
                             for (a, ta), (b, tb) in zip(marks, marks[1:])})
    sock.close()
    print(json.dumps({"elapsed": time.perf_counter() - t0,
                      "ack_times": ack_times, "hop_rows": hop_rows,
                      "nacked": nacked,
                      "jax_imported": "jax" in sys.modules}), flush=True)


if __name__ == "__main__":
    main()
