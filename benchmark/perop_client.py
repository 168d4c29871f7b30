"""Per-op SharedMatrix load client of the benchmark: the writers of one
document, each on its own TCP connection to the serving front door,
speaking the JSON request protocol (``connect``, ``submit``,
``get_deltas``) and timing when each writer sees its own ops sequenced.

Run as a plain script: it imports neither the program nor JAX (the
serving process owns the chip), only numpy and the benchmark's plain
reference (``ref_matrix.py``), whose permutation vectors give each writer
the row and col counts of its own view. Protocol on stdin/stdout:

1. one JSON config line in; the client connects its writers, loads the
   document's sequenced history once (``get_deltas``) and prints
   ``READY``;
2. ``WARM <n>`` in: every writer makes ``n`` submits, closed loop; the
   client prints ``WARMED`` once all are sequenced;
3. ``GO <t0> <t_end>`` in (monotonic clock, ``time.monotonic``): from
   ``t0`` each writer keeps ``window`` submits in flight until ``t_end``;
   then the client waits for the submits in flight (at most ``DRAIN_S``
   past ``t_end``) and prints one JSON result line.

Config: ``port``, ``doc``, ``writers``, ``seed`` (list of ints: the
generator's seed sequence), ``ops_per_submit``, ``window``, ``mix``
({kind: share} over set_cell, insert_rows, remove_rows, insert_cols,
remove_cols), ``span`` ([lo, hi] rows or cols a structural op moves),
``datastore`` and ``channel`` (the matrix's address).

A writer counts its submit acked when an ``ops`` event on its own
connection carries the submit's last op, sequenced. Result: ``writers``
([client_id, ops sent, ops acked]), ``submits`` ([sent, acked or null,
ops] per window submit), ``nacks``.
"""

import json
import selectors
import socket
import struct
import sys
import time

import numpy as np

import ref_matrix

DRAIN_S = 60.0
OPERATION = 8       # protocol MessageType.OPERATION
KINDS = ("set_cell", "insert_rows", "remove_rows", "insert_cols",
         "remove_cols")


class Conn:
    """One writer: its socket, its read buffer and its submit state."""

    def __init__(self, sock: socket.socket) -> None:
        self.sock = sock
        self.buf = bytearray()
        self.client_id = None
        self.ref = 0            # last seq seen on this connection
        self.cseq = 0           # last client sequence number used
        self.inflight: list = []   # [last cseq, sent, n ops, record]
        self.sent = self.acked = 0
        self.quota = 0          # warm-up submits still to make
        self.rng = None

    def send(self, payload: dict) -> None:
        body = json.dumps(payload, separators=(",", ":")).encode()
        self.sock.sendall(struct.pack(">I", len(body)) + body)

    def frames(self):
        """Complete frames read so far (bodies, in order)."""
        out = []
        while len(self.buf) >= 4:
            n = struct.unpack_from(">I", self.buf)[0]
            if len(self.buf) < 4 + n:
                break
            out.append(bytes(self.buf[4:4 + n]))
            del self.buf[:4 + n]
        return out


class Client:
    def __init__(self, cfg: dict) -> None:
        self.cfg = cfg
        self.doc = cfg["doc"]
        self.k = int(cfg["ops_per_submit"])
        self.window = int(cfg.get("window", 1))
        shares = np.asarray([cfg["mix"].get(k, 0.0) for k in KINDS])
        self.cdf = np.cumsum(shares / shares.sum())
        self.span = cfg["span"]
        self.matrix = ref_matrix.Matrix()   # structural ops only
        self.applied = 0                    # seq the replica holds
        self.pending: dict[int, tuple] = {}
        self.events: dict[int, tuple] = {}  # hash of body -> parsed
        self.sel = selectors.DefaultSelector()
        self.conns: list[Conn] = []
        self.submits: list = []
        self.nacks = 0
        self.timing = False

    # -- wire ------------------------------------------------------------------

    def connect(self) -> None:
        for i in range(int(self.cfg["writers"])):
            sock = socket.create_connection(("127.0.0.1", self.cfg["port"]))
            sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            conn = Conn(sock)
            conn.rng = np.random.default_rng(
                [x % (1 << 63) for x in self.cfg["seed"]] + [i])
            self.conns.append(conn)
            self.sel.register(sock, selectors.EVENT_READ, conn)
            conn.send({"rid": 0, "op": "connect", "doc_id": self.doc,
                       "mode": "write"})
        # Each writer has seen the round that sequenced its join.
        self.pump_until(lambda: all(c.client_id and c.ref
                                    for c in self.conns), 120)
        first = self.conns[0]
        first.send({"rid": 1, "op": "get_deltas", "doc_id": self.doc,
                    "from_seq": 0})
        self.loaded = False
        self.pump_until(lambda: self.loaded, 120)

    def pump_until(self, done, timeout: float) -> None:
        deadline = time.monotonic() + timeout
        while not done():
            left = deadline - time.monotonic()
            if left <= 0:
                raise TimeoutError("the server did not answer in time")
            self.pump(min(left, 0.05))

    def pump(self, wait: float) -> None:
        for key, _ in self.sel.select(wait):
            conn = key.data
            chunk = conn.sock.recv(1 << 20)
            if not chunk:
                raise EOFError("server closed a connection")
            conn.buf += chunk
            for body in conn.frames():
                self.on_frame(conn, body)

    def on_frame(self, conn: Conn, body: bytes) -> None:
        if body.startswith(b'{"event":"ops"'):
            self.on_ops(conn, body)
            return
        msg = json.loads(body)
        if msg.get("event") == "nack":
            self.nacks += 1
            if conn.inflight:
                conn.inflight.pop(0)
            return
        if msg.get("error"):
            raise RuntimeError(f"server error: {msg['error']}")
        if msg.get("rid") == 0 and "client_id" in msg:
            conn.client_id = msg["client_id"]
        elif msg.get("rid") == 1 and "messages" in msg:
            self.feed([(m["sequence_number"],
                        m["reference_sequence_number"], m["client_id"],
                        m["type"], m["contents"]) for m in msg["messages"]])
            self.loaded = True

    def on_ops(self, conn: Conn, body: bytes) -> None:
        key = hash(body)
        parsed = self.events.get(key)
        if parsed is None:
            msgs = json.loads(body)["messages"]
            ops = [(m["sequence_number"], m["reference_sequence_number"],
                    m["client_id"], m["type"], m["contents"]) for m in msgs]
            # Seq order: the last cseq of each writer in this event.
            last = {m["client_id"]: m["client_sequence_number"]
                    for m in msgs if m["type"] == OPERATION}
            parsed = (ops[-1][0], last)
            self.events[key] = parsed
            if len(self.events) > 64:
                self.events.pop(next(iter(self.events)))
            self.feed(ops)
        seq, last = parsed
        conn.ref = max(conn.ref, seq)
        top = last.get(conn.client_id)
        now = time.monotonic()
        while conn.inflight and top is not None \
                and conn.inflight[0][0] <= top:
            _cseq, _sent, n, rec = conn.inflight.pop(0)
            conn.acked += n
            if rec is not None:
                rec[1] = now
        self.refill(conn)

    def feed(self, ops) -> None:
        """Apply the doc's sequenced structural ops to the replica, in
        seq order (each connection delivers every op; the first wins)."""
        for op in ops:
            if op[0] > self.applied:
                self.pending[op[0]] = op
        while self.applied + 1 in self.pending:
            seq, ref, client, mtype, contents = self.pending.pop(
                self.applied + 1)
            self.applied = seq
            if mtype != OPERATION:
                continue
            channel_op = contents["contents"]["contents"]
            if channel_op["target"] != "cell":
                self.matrix.apply(seq, ref, client, channel_op)

    # -- load ------------------------------------------------------------------

    def refill(self, conn: Conn) -> None:
        while len(conn.inflight) < self.window and (
                conn.quota > 0 or (self.timing
                                   and time.monotonic() < self.t_end)):
            if conn.quota > 0:
                conn.quota -= 1
            self.submit(conn)

    def submit(self, conn: Conn) -> None:
        """``ops_per_submit`` ops in the writer's view at its ref: each op
        sees the writer's earlier ops of the same submit."""
        client = self.matrix._clients.setdefault(
            conn.client_id, len(self.matrix._clients))
        rows = int(self.matrix.rows._vis(conn.ref, client).sum())
        cols = int(self.matrix.cols._vis(conn.ref, client).sum())
        rng = conn.rng
        messages = []
        for _ in range(self.k):
            kind = KINDS[int(np.searchsorted(self.cdf, rng.random(),
                                             side="right"))]
            n = int(rng.integers(self.span[0], self.span[1] + 1))
            axis = "rows" if kind.endswith("rows") else "cols"
            have = rows if axis == "rows" else cols
            if kind == "set_cell" and not (rows and cols):
                # Concurrent removes emptied an axis of this view.
                axis = "rows" if not rows else "cols"
                kind, have = "insert_" + axis, rows if not rows else cols
            if kind.startswith("remove") and have <= n:
                kind = "insert_" + axis
            if kind == "set_cell":
                op = {"target": "cell", "type": "set",
                      "row": int(rng.integers(rows)),
                      "col": int(rng.integers(cols)),
                      "value": int(rng.integers(1, 1 << 31))}
            elif kind.startswith("insert"):
                op = {"target": axis, "type": "insert",
                      "pos": int(rng.integers(have + 1)), "count": n}
                have += n
            else:
                start = int(rng.integers(have - n + 1))
                op = {"target": axis, "type": "remove", "start": start,
                      "end": start + n}
                have -= n
            if axis == "rows":
                rows = have
            else:
                cols = have
            conn.cseq += 1
            messages.append({
                "_t": "doc", "client_sequence_number": conn.cseq,
                "reference_sequence_number": conn.ref, "type": OPERATION,
                "contents": {"address": self.cfg["datastore"],
                             "contents": {"address": self.cfg["channel"],
                                          "contents": op}},
                "metadata": None, "server_metadata": None, "traces": []})
        now = time.monotonic()
        rec = [now, None, self.k] if self.timing else None
        if rec is not None:
            self.submits.append(rec)
        conn.inflight.append([conn.cseq, now, self.k, rec])
        conn.sent += self.k
        conn.send({"rid": 2, "op": "submit", "messages": messages})

    def warm(self, n: int) -> None:
        for conn in self.conns:
            conn.quota = n
            self.refill(conn)
        self.pump_until(lambda: all(not c.inflight and c.quota == 0
                                    for c in self.conns), 300)

    def run(self, t0: float, t_end: float) -> dict:
        self.t_end = t_end
        while time.monotonic() < t0:
            time.sleep(min(0.01, max(0.0, t0 - time.monotonic())))
        self.timing = True
        for conn in self.conns:
            self.refill(conn)
        while time.monotonic() < t_end:
            self.pump(min(0.05, max(0.0, t_end - time.monotonic())))
        self.timing = False
        deadline = t_end + DRAIN_S
        while any(c.inflight for c in self.conns) \
                and time.monotonic() < deadline:
            self.pump(0.05)
        return {"writers": [[c.client_id, c.sent, c.acked]
                            for c in self.conns],
                "submits": self.submits, "nacks": self.nacks}


def main() -> None:
    client = Client(json.loads(sys.stdin.readline()))
    client.connect()
    print("READY", flush=True)
    while True:
        words = sys.stdin.readline().split()
        if words[0] == "WARM":
            client.warm(int(words[1]))
            print("WARMED", flush=True)
        elif words[0] == "GO":
            result = client.run(float(words[1]), float(words[2]))
            print(json.dumps(result), flush=True)
            return


if __name__ == "__main__":
    main()
