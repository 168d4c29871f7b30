"""Driver of the storm front door: storm frames over TCP from JAX-free load
clients (``benchmark/storm_client.py``) into the C++ bridge, the
``StormController`` (admission, compose, the fused sequencer + map-fold
tick) and the group-commit WAL; acks go back only after the tick's fsync.

The traffic file sets the loop: ``closed`` (each connection keeps
``window`` frames in flight) or ``open`` (each connection sends one frame
every 1 / ``frames_per_s`` seconds, phase-offset across connections).
Every frame carries all of its connection's docs x ``k`` ops.

The check, once the window has closed and every client has its acks:
per-doc sequencing and content (the durable WAL records of sampled docs,
in seq order, are contiguous and carry exactly the words the clients sent,
in the order their frames were acked), the map state of sampled docs
against the plain LWW reference, acked ops all in the durable WAL, and no
frame left unanswered.
"""

from __future__ import annotations

import json
import os
import random
import struct
import subprocess
import sys
import time
from pathlib import Path

import numpy as np

from benchmark import ref_map
from benchmark.harness import percentile
from benchmark.storm_client import make_bodies

CLIENT = Path(__file__).resolve().parents[1] / "storm_client.py"


def require_native() -> None:
    """The served path needs the C++ bridge, fan-out and oplog: no silent
    fallback to the Python twins."""
    from fluidframework_tpu import native
    from fluidframework_tpu.native import _loader, bridge, fanout

    for name, load in (("bridge", bridge._load_library),
                       ("fanout", fanout._load_library),
                       ("oplog", native._load_library)):
        if load() is None:
            raise RuntimeError(f"native {name} did not build: "
                               f"{_loader.build_errors.get(name)}")


class Driver:
    def __init__(self, config, traffic, seed, devices, scratch):
        self.traffic, self.seed, self.scratch = traffic, seed, scratch
        self.sizes = config["sizes"]
        self.procs: list[subprocess.Popen] = []
        self.front = None
        self.storm = None

    # -- set-up ----------------------------------------------------------------

    def setup(self) -> None:
        require_native()
        from fluidframework_tpu.native.fanout import make_fanout
        from fluidframework_tpu.server.bridge_host import BridgeFrontDoor
        from fluidframework_tpu.server.kernel_host import KernelSequencerHost
        from fluidframework_tpu.server.merge_host import KernelMergeHost
        from fluidframework_tpu.server.routerlicious import (
            RouterliciousService)
        from fluidframework_tpu.server.storm import StormController

        s, t = self.sizes, self.traffic
        n, cpd, k = s["docs"], s["clients_per_doc"], t["k"]
        self.seq_host = KernelSequencerHost(num_slots=cpd,
                                            initial_capacity=n)
        self.merge_host = KernelMergeHost(
            map_slots=s["key_slots"], row_capacity=n, flush_threshold=10**9)
        self.service = RouterliciousService(
            merge_host=self.merge_host, batched_deli_host=self.seq_host,
            auto_pump=False, fanout=make_fanout())
        self.storm = StormController(
            self.service, self.seq_host, self.merge_host,
            flush_threshold_docs=s["flush_threshold_docs"],
            max_key_slots=s["key_slots"],
            spill_dir=os.path.join(self.scratch, "wal"),
            durability="group")
        self.front = BridgeFrontDoor(self.service, 0)
        self.docs = [f"storm-doc-{i}" for i in range(n)]
        self.clients = {d: [self.service.connect(d, _drop).client_id
                            for _ in range(cpd)] for d in self.docs}
        self.service.pump()

        # Warm-up: full-shape ticks in process through client 0 of every
        # doc compile the fused deli + map-fold program.
        self.warm = make_bodies([self.seed, 1 << 20], n, k,
                                s["key_slots"], t["kinds"],
                                t["warm_ticks"])
        for i, words in enumerate(self.warm):
            self.storm.submit_frame(None, {"op": "storm", "docs": [
                [d, self.clients[d][0], 1 + i * k, 1, k]
                for d in self.docs]}, memoryview(words.tobytes()))
            self.storm.flush()
        if self.storm.stats["sequenced_ops"] != n * k * len(self.warm):
            raise RuntimeError("warm-up ticks did not sequence every op")
        # The service's periodic idle-client check runs inside the window
        # too; its programs compile here.
        self.service.eject_idle_clients()

        conns = t["connections"]
        per = n // conns
        self.conn_docs = [self.docs[c * per:(c + 1) * per]
                          for c in range(conns)]
        for c, docs in enumerate(self.conn_docs):
            cfg = {"port": self.front.port, "k": k,
                   "seed": [self.seed, c],
                   "num_slots": s["key_slots"], "kinds": t["kinds"],
                   "bodies": t["bodies"], "loop": t["loop"],
                   "docs": [[d, self.clients[d]] for d in docs],
                   "cseq0": [[1 + len(self.warm) * k] + [1] * (cpd - 1)]
                   * len(docs)}
            if t["loop"] == "open":
                cfg["frames_per_s"] = t["frames_per_s"]
                cfg["phase_s"] = c / (conns * t["frames_per_s"])
            else:
                cfg["window"] = t["window"]
            proc = subprocess.Popen(
                [sys.executable, str(CLIENT)], stdin=subprocess.PIPE,
                stdout=subprocess.PIPE, text=True,
                env={**os.environ, "JAX_PLATFORMS": "cpu"})
            self.procs.append(proc)
            proc.stdin.write(json.dumps(cfg) + "\n")
            proc.stdin.flush()
        for proc in self.procs:
            if proc.stdout.readline().strip() != "READY":
                raise RuntimeError("a load client did not start")
        self.storm.ledger.clear()
        self.base_stats = dict(self.storm.stats)

    # -- window ----------------------------------------------------------------

    def window(self, seconds: float, trace) -> None:
        t0 = time.monotonic() + 0.1
        t_end = t0 + seconds
        for proc in self.procs:
            proc.stdin.write(f"GO {t0!r} {t_end!r}\n")
            proc.stdin.flush()
        if trace is not None:
            _sleep_until(t0 + 0.4 * seconds)
            trace.start()
            _sleep_until(min(t_end, time.monotonic()
                             + self.traffic["trace_s"]))
            trace.stop()
        _sleep_until(t_end)
        self.window_ticks = {r["tick"] for r in self.storm.ledger.records()}
        self.results = [json.loads(proc.stdout.readline())
                        for proc in self.procs]
        for proc in self.procs:
            proc.wait(timeout=30)
        self.t0, self.t_end = t0, t_end

    def collect(self) -> None:
        storm = self.storm
        storm.flush()
        self.ledger = [r for r in storm.ledger.records()
                       if r["tick"] in self.window_ticks]
        self.stats = {key: storm.stats[key] - self.base_stats.get(key, 0)
                      for key in storm.stats}
        rng = random.Random(self.seed ^ 0x5EED)
        pick = rng.sample(range(len(self.docs)), self.sizes["sample"])
        self.pick = {self.docs[i]: i for i in pick}
        self.entries = {d: self.merge_host.map_entries(d, "default", "root")
                        for d in self.pick}
        # The durable WAL: every record below the durability watermark.
        wal = storm._group_wal
        self.durable_ops: dict[str, int] = {}
        self.records: dict[str, list] = {d: [] for d in self.pick}
        for i in range(wal.durable_len):
            blob = bytes(wal.read(i))
            hlen = struct.unpack_from("<I", blob)[0]
            header = json.loads(blob[4:4 + hlen])
            for doc, _c, _c0, _r, count, ns, fs, ls, _m, w_off in \
                    header["docs"]:
                self.durable_ops[doc] = self.durable_ops.get(doc, 0) + ns
                if doc in self.records:
                    off = 4 + hlen + w_off
                    words = (np.frombuffer(blob, np.uint32, count, off)
                             .copy() if off + 4 * count <= len(blob)
                             else None)  # a record cut short
                    self.records[doc].append((fs, ls, ns, count, words))
        self._shutdown()

    # -- results ---------------------------------------------------------------

    def data(self) -> dict:
        from benchmark import kernel_bytes

        t0, t_end = self.t0, self.t_end
        k = self.traffic["k"]
        # The rate's window ends at the last ack before t_end: all the
        # work of whole ticks, and all the time they took from t0.
        end = max((f[3] for res in self.results for f in res["frames"]
                   if f[3] is not None and f[3] <= t_end), default=t_end)
        ops, lat, late, attempted, failed = 0, [], [], 0, 0
        for res in self.results:
            per_frame = res["n_docs"] * k
            for _rid, due, sent, acked in res["frames"]:
                if acked is not None and acked <= end:
                    ops += per_frame
                if self.traffic["loop"] == "open":
                    if due >= t_end:
                        continue
                    late.append(1000.0 * (sent - due))
                    if acked is not None:
                        lat.append(1000.0 * (acked - due))
                elif acked is not None and acked <= t_end:
                    lat.append(1000.0 * (acked - sent))
                attempted += 1
                failed += acked is None
        s = self.sizes
        return {
            "window_s": end - t0, "ops": ops,
            "rate_thirds": self._thirds(end),
            "attempted": attempted, "failed": failed,
            "latencies_ms": lat, "lateness_ms": late,
            "ledger": self.ledger, "counters": self.stats, "host": {},
            "kernels": {"map_fold": "_storm_tick"},
            "kernel_bytes": {"map_fold": kernel_bytes.map_fold_bytes(
                s["docs"], k, s["key_slots"])},
        }

    def _thirds(self, end: float) -> dict:
        """Closed loop: ops acked per second in each third of the rate's
        window. Open loop: the median ack (ms) of the frames due in each
        third; a backlog that grows shows as a rising median."""
        k, t0 = self.traffic["k"], self.t0
        if self.traffic["loop"] == "open":
            span = (self.t_end - t0) / 3
            lat = [[], [], []]
            for res in self.results:
                for _rid, due, _sent, acked in res["frames"]:
                    if acked is not None and due < self.t_end:
                        lat[min(2, int((due - t0) / span))].append(
                            1000.0 * (acked - due))
            return {"ack_ms_p50": [percentile(x, 0.5) if x else None
                                   for x in lat]}
        span = (end - t0) / 3
        ops = [0, 0, 0]
        for res in self.results:
            for _rid, _due, _sent, acked in res["frames"]:
                if acked is not None and acked <= end:
                    ops[min(2, int((acked - t0) / span))] += \
                        res["n_docs"] * k
        return {"ops_per_s": [n / span for n in ops]}

    def _expected(self) -> dict[str, list]:
        """Per sampled doc, the word batches the clients had acked, in
        ack order, after the warm-up batches."""
        s, t = self.sizes, self.traffic
        out = {}
        for c, docs in enumerate(self.conn_docs):
            mine = [d for d in docs if d in self.pick]
            if not mine:
                continue
            bodies = make_bodies([self.seed, c], len(docs),
                                 t["k"], s["key_slots"], t["kinds"],
                                 t["bodies"])
            order = self.results[c]["order"]
            for d in mine:
                j = docs.index(d)
                doc_i = self.pick[d]
                out[d] = ([w[doc_i] for w in self.warm]
                          + [bodies[rid % len(bodies)][j] for rid in order])
        return out

    def check(self) -> dict:
        expected = self._expected()
        k = self.traffic["k"]
        seq_bad = map_bad = 0
        for d, batches in expected.items():
            recs = sorted(self.records[d])
            ok = len(recs) == len(batches) and all(
                ns == count for _fs, _ls, ns, count, _w in recs)
            ok = ok and all(b[0] == a[1] + 1 for a, b in zip(recs, recs[1:]))
            ok = ok and all(r[4] is not None and np.array_equal(r[4], b)
                            for r, b in zip(recs, batches))
            seq_bad += not ok
            map_bad += ref_map.lww_fold(batches) != self.entries[d]
        acked = {}
        for c, docs in enumerate(self.conn_docs):
            n_acked = len(self.results[c]["order"]) + len(self.warm)
            for d in docs:
                acked[d] = n_acked * k
        missing = sum(max(0, n - self.durable_ops.get(d, 0))
                      for d, n in acked.items())
        unanswered = sum(1 for res in self.results
                         for f in res["frames"] if f[3] is None)
        return {"unacked_frames": unanswered, "seq_bad_docs": seq_bad,
                "map_bad_docs": map_bad, "acked_not_durable_ops": missing}

    def use_control(self) -> None:
        """The control in the program's place: the reference's map state
        of every sampled doc with per-doc total order broken (its last two
        acked batches folded in swapped order)."""
        self.entries = {d: ref_map.lww_fold(ref_map.swap_last(batches))
                        for d, batches in self._expected().items()}

    # -- teardown --------------------------------------------------------------

    def _shutdown(self) -> None:
        for proc in self.procs:
            if proc.poll() is None:
                proc.kill()
            proc.wait()
        if self.front is not None:
            self.front.close()
            self.front = None
        if self.storm is not None and self.storm._group_wal is not None:
            self.storm._group_wal.close()
        self.storm = self.service = self.merge_host = self.seq_host = None

    def close(self) -> None:
        self._shutdown()


def _drop(_msgs) -> None:
    pass


def _sleep_until(t: float) -> None:
    while True:
        left = t - time.monotonic()
        if left <= 0:
            return
        time.sleep(min(left, 0.05))
