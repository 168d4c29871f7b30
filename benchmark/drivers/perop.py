"""Driver of the per-op path: SharedMatrix writers on JAX-free load clients
(``benchmark/perop_client.py``), one TCP connection each, into the C++
bridge front door, which serves them in rounds: every request ready in a
poll pass, then one ``RouterliciousService.pump`` — the device deli
(``KernelSequencerHost``), the durable log (``DurableMessageBus``,
``FileStateStore``), one broadcast batch per document, and the merger's
``KernelMergeHost`` with its one matrix tick per round.

The grids are inserted in process before the clients connect (rows, then
cols, by a setup client that then leaves, so that the window of every
document is set by its writers alone). The clients warm up with closed-
loop rounds; every device program the window can use is compiled first.

The check, once the window has closed and every writer has its submits
answered: no op left unacked, the durable op log of every document with
contiguous seqs and each writer's ops once and in client-seq order, every
document's grid from the merge host against the plain reference's replay
of that log, and no grid on the scalar fallback.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time
from pathlib import Path

from benchmark import ref_matrix
from benchmark.drivers.storm import _drop, _sleep_until, require_native

CLIENT = Path(__file__).resolve().parents[1] / "perop_client.py"
DATASTORE, CHANNEL = "default", "grid"


class Driver:
    def __init__(self, config, traffic, seed, devices, scratch):
        self.traffic, self.seed, self.scratch = traffic, seed, scratch
        self.sizes = config["sizes"]
        self.procs: list[subprocess.Popen] = []
        self.front = None
        self.service = None

    # -- set-up ----------------------------------------------------------------

    def setup(self) -> None:
        require_native()
        from fluidframework_tpu.protocol.messages import (DocumentMessage,
                                                          MessageType)
        from fluidframework_tpu.server.bridge_host import BridgeFrontDoor
        from fluidframework_tpu.server.durable_store import (
            DurableMessageBus, FileStateStore)
        from fluidframework_tpu.server.kernel_host import KernelSequencerHost
        from fluidframework_tpu.server.merge_host import KernelMergeHost
        from fluidframework_tpu.server.routerlicious import (
            RouterliciousService)

        s, t = self.sizes, self.traffic
        n_docs, writers = s["docs"], s["writers_per_doc"]
        self.bus = DurableMessageBus(os.path.join(self.scratch, "bus"))
        self.store = FileStateStore(os.path.join(self.scratch, "state"))
        self.seq_host = KernelSequencerHost(num_slots=s["client_slots"],
                                            initial_capacity=n_docs)
        self.merge_host = KernelMergeHost(
            row_capacity=n_docs, flush_threshold=10**9,
            matrix_vec_slots=s["vec_slots"], matrix_handles=s["handles"],
            matrix_writers=writers + 1)
        self.service = RouterliciousService(
            bus=self.bus, store=self.store, merge_host=self.merge_host,
            batched_deli_host=self.seq_host, auto_pump=False)
        self.docs = [f"grid-{i}" for i in range(n_docs)]

        # The grids: rows then cols, by a setup client that then leaves.
        for d in self.docs:
            conn = self.service.connect(d, _drop)
            self.service.pump()
            ref = self.service.get_deltas(d, 0)[-1].sequence_number
            conn.submit([DocumentMessage(
                client_sequence_number=i + 1, reference_sequence_number=ref,
                type=MessageType.OPERATION,
                contents={"address": DATASTORE, "contents": {
                    "address": CHANNEL, "contents": {
                        "target": axis, "type": "insert", "pos": 0,
                        "count": s[axis]}}})
                for i, axis in enumerate(("rows", "cols"))])
            self.service.pump()
            conn.close()
        self.service.pump()
        # Every device program the window can reach compiles now: each
        # deli tick width, each matrix tick shape, the idle-client check.
        self.seq_host.warm_ticks(writers * t["window"]
                                 * t["ops_per_submit"] + s["client_slots"])
        self.merge_host.warm_matrix_ticks()
        self.service.eject_idle_clients()

        self.front = BridgeFrontDoor(self.service, 0)
        procs_per_doc = s["client_procs"] // n_docs
        per_proc = writers // procs_per_doc
        self.proc_doc = []
        for p in range(s["client_procs"]):
            doc = self.docs[p // procs_per_doc]
            cfg = {"port": self.front.port, "doc": doc,
                   "writers": per_proc, "seed": [self.seed, p],
                   "ops_per_submit": t["ops_per_submit"],
                   "window": t["window"], "mix": t["mix"],
                   "span": t["span"], "datastore": DATASTORE,
                   "channel": CHANNEL}
            proc = subprocess.Popen(
                [sys.executable, str(CLIENT)], stdin=subprocess.PIPE,
                stdout=subprocess.PIPE, text=True,
                env={**os.environ, "JAX_PLATFORMS": "cpu"})
            self.procs.append(proc)
            self.proc_doc.append(doc)
            proc.stdin.write(json.dumps(cfg) + "\n")
            proc.stdin.flush()
        self._expect("READY")
        self._tell(f"WARM {t['warm_rounds']}")
        self._expect("WARMED")
        self.base = self._counters()
        self.service.ledger.clear()

    def _tell(self, line: str) -> None:
        for proc in self.procs:
            proc.stdin.write(line + "\n")
            proc.stdin.flush()

    def _expect(self, word: str) -> None:
        for proc in self.procs:
            got = proc.stdout.readline().strip()
            if got != word:
                raise RuntimeError(f"a load client said {got!r}, not "
                                   f"{word}")

    def _counters(self) -> dict:
        snap = self.service.metrics.snapshot()
        return {k: v for k, v in snap.items()
                if isinstance(v, (int, float))}

    # -- window ----------------------------------------------------------------

    def window(self, seconds: float, trace) -> None:
        t0 = time.monotonic() + 0.1
        t_end = t0 + seconds
        self._tell(f"GO {t0!r} {t_end!r}")
        if trace is not None:
            _sleep_until(t0 + 0.4 * seconds)
            trace.start()
            _sleep_until(min(t_end, time.monotonic()
                             + self.traffic["trace_s"]))
            trace.stop()
        _sleep_until(t_end)
        self.window_rounds = {r["tick"]
                              for r in self.service.ledger.records()}
        self.results = [json.loads(proc.stdout.readline())
                        for proc in self.procs]
        now = self._counters()
        self.stats = {k: now[k] - self.base.get(k, 0) for k in now}
        for proc in self.procs:
            proc.wait(timeout=30)
        self.t0, self.t_end = t0, t_end

    def collect(self) -> None:
        # The clients are done: stop the serving thread, then read.
        self.front.close()
        self.front = None
        self.service.pump()
        self.ledger = [r for r in self.service.ledger.records()
                       if r["tick"] in self.window_rounds]
        # The program's counters over the window and its drain, beside
        # the harness's own log lines (the result line has no place).
        print("counters " + json.dumps({k: self.stats.get(k, 0) for k in (
            "matrix.step_ticks", "matrix.ops_device", "matrix.ops_scalar",
            "matrix.state_grows", "deli.device_ticks",
            "broadcast.frames_pushed")}), file=sys.stderr)
        self.store.sync()
        host = self.merge_host
        self.logs = {d: self.store.get(f"ops/{d}", []) for d in self.docs}
        self.grids = {d: host.matrix_grid(d, DATASTORE, CHANNEL)
                      for d in self.docs}
        self.scalar_docs = sum(
            host.matrix_path(d, DATASTORE, CHANNEL) == "scalar"
            for d in self.docs)
        self._shutdown()

    # -- results ---------------------------------------------------------------

    def data(self) -> dict:
        from benchmark import matrix_bytes

        t0, t_end = self.t0, self.t_end
        subs = [(sent, acked, n) for res in self.results
                for sent, acked, n in res["submits"]]
        # The rate's window ends at the last ack before t_end, one end for
        # all writers: the ops acked by then, over all the time from t0.
        end = max((a for _s, a, _n in subs if a is not None and a <= t_end),
                  default=t_end)
        ops = sum(n for _s, a, n in subs if a is not None and a <= end)
        lat = [1000.0 * (a - s) for s, a, _n in subs
               if a is not None and a <= t_end]
        span = (end - t0) / 3
        thirds = [0, 0, 0]
        for _s, a, n in subs:
            if a is not None and a <= end:
                thirds[min(2, int((a - t0) / span))] += n
        s = self.sizes
        t_max, n_max = _largest_tick()
        return {
            "window_s": end - t0, "ops": ops,
            "rate_thirds": {"ops_per_s": [n / span for n in thirds]},
            "attempted": len(subs),
            "failed": sum(a is None for _s, a, _n in subs),
            "latencies_ms": lat, "lateness_ms": [],
            "ledger": self.ledger, "counters": self.stats, "host": {},
            "kernels": {"matrix_apply": "matrix_tick"},
            "kernel_bytes": {"matrix_apply": matrix_bytes.matrix_tick_bytes(
                s["docs"], s["vec_slots"], s["writers_per_doc"] + 1,
                t_max, n_max)},
            "trace_dir": os.path.join(self.scratch, "trace"),
        }

    def _ops_of(self, doc: str) -> list:
        from fluidframework_tpu.protocol.messages import MessageType

        return [(m.sequence_number, m.reference_sequence_number,
                 m.client_id, m.contents["contents"]["contents"])
                for m in self.logs[doc]
                if m.type == MessageType.OPERATION]

    def check(self) -> dict:
        from fluidframework_tpu.protocol.messages import MessageType

        writers = {}
        for res in self.results:
            for client_id, sent, acked in res["writers"]:
                writers[client_id] = (sent, acked)
        unacked = sum(sent - acked for sent, acked in writers.values())
        seq_bad = grid_bad = 0
        for d in self.docs:
            log = self.logs[d]
            seqs = [m.sequence_number for m in log]
            ok = seqs == list(range(seqs[0], seqs[0] + len(seqs)))
            cseqs: dict = {}
            for m in log:
                if m.type == MessageType.OPERATION and m.client_id in writers:
                    cseqs.setdefault(m.client_id, []).append(
                        m.client_sequence_number)
            for client_id, got in cseqs.items():
                sent, acked = writers[client_id]
                ok = ok and got == list(range(1, len(got) + 1)) \
                    and acked <= len(got) <= sent
            seq_bad += not ok
            grid_bad += ref_matrix.replay(self._ops_of(d)) != self.grids[d]
        return {"unacked_ops": unacked, "seq_bad_docs": seq_bad,
                "grid_bad_docs": grid_bad,
                "scalar_served_docs": self.scalar_docs}

    def use_control(self) -> None:
        """The control in the program's place: the reference's grid of
        every document with per-doc total order broken (its last pair of
        causally ordered structural ops applied the other way round)."""
        self.grids = {d: ref_matrix.replay(
            ref_matrix.swap_last_structural(self._ops_of(d)))
            for d in self.docs}

    # -- teardown --------------------------------------------------------------

    def _shutdown(self) -> None:
        for proc in self.procs:
            if proc.poll() is None:
                proc.kill()
            proc.wait()
        if self.front is not None:
            self.front.close()
            self.front = None
        if self.service is not None:
            self.bus.close()
            self.store.close()
        self.service = self.merge_host = self.seq_host = None

    def close(self) -> None:
        self._shutdown()


def _largest_tick() -> tuple[int, int]:
    from fluidframework_tpu.server.merge_host import KernelMergeHost

    return KernelMergeHost.MATRIX_TICK_SHAPES[-1]

