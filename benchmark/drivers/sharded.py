"""Driver of the sharded serving entry: ``ShardedServing`` over a
docs-sharded mesh of the cell's chips, the Pallas map fold under
``shard_map``, one ``submit`` per row per tick, then ``tick`` and
``flush`` (the durable log is the assembly's in-memory one).

Closed loop: each tick submits ``k`` map ops for every row, runs the
fused tick and harvests every row's ack. The window runs whole ticks
until its time is up; ops per second counts the ops of every acked row of
the ticks it finished, over all of its time. The check compares sampled
rows of every shard with the plain LWW reference of the words submitted.
"""

from __future__ import annotations

import random
import time

import numpy as np

from benchmark import ref_map
from benchmark.storm_client import make_bodies


class Driver:
    def __init__(self, config, traffic, seed, devices, scratch):
        self.traffic, self.seed, self.devices = traffic, seed, devices
        self.sizes = config["sizes"]
        self.serving = None
        self.ticks = 0
        self.unacked = 0
        self.tick_s: list[float] = []  # tick + flush, per tick
        self.wall_s: list[float] = []  # submit loop to harvest, per tick

    def setup(self) -> None:
        from fluidframework_tpu.parallel.mesh import make_mesh
        from fluidframework_tpu.parallel.serving import ShardedServing

        s, t = self.sizes, self.traffic
        n_dev = len(self.devices)
        self.num_docs = s["docs_per_chip"] * n_dev
        self.serving = ShardedServing(
            make_mesh(self.devices), num_docs=self.num_docs, k=t["k"],
            num_hosts=n_dev, num_clients=s["clients_per_doc"],
            map_slots=s["key_slots"])
        self.serving.join_all()
        self.bodies = make_bodies([self.seed, 0],
                                  self.num_docs, t["k"], s["key_slots"],
                                  t["kinds"], t["bodies"])
        # Sampled rows: an equal share from every chip's doc range.
        rng = random.Random(self.seed ^ 0x5EED)
        per = self.num_docs // n_dev
        self.pick = sorted(r for d in range(n_dev) for r in rng.sample(
            range(d * per, (d + 1) * per), s["sample"] // n_dev))
        for _ in range(t["warm_ticks"]):
            self._tick()
        self.tick_s.clear()
        self.wall_s.clear()

    def _tick(self) -> None:
        k = self.traffic["k"]
        words = self.bodies[self.ticks % len(self.bodies)]
        cseq, ref = 1 + self.ticks * k, 1 + self.ticks * k
        serving = self.serving
        wall0 = time.perf_counter()
        for row in range(self.num_docs):
            serving.submit(row, words[row], cseq, ref)
        t0 = time.perf_counter()
        harvest = serving.tick()
        harvest = serving.flush() or [harvest]
        self.tick_s.append(time.perf_counter() - t0)
        acked = 0
        for per_host in harvest:
            for rows in per_host.values():
                acked += sum(1 for a in rows.values() if a[0] == k)
        self.unacked += self.num_docs - acked
        self.ticks += 1
        self.wall_s.append(time.perf_counter() - wall0)

    def window(self, seconds: float, trace) -> None:
        start = self.ticks
        t0 = time.perf_counter()
        traced_from = None
        while True:
            elapsed = time.perf_counter() - t0
            if elapsed >= seconds:
                break
            if trace is not None:
                if traced_from is None and elapsed >= 0.4 * seconds:
                    trace.start()
                    traced_from = self.ticks
                elif traced_from is not None and \
                        self.ticks - traced_from >= 2:
                    trace.stop()
            self._tick()
        self.window_s = time.perf_counter() - t0
        self.window_ticks = self.ticks - start
        if trace is not None:
            trace.stop()

    def collect(self) -> None:
        state = self.serving.map_state
        self.shard_devices = len({sh.device
                                  for sh in state.value.addressable_shards})
        values = np.asarray(state.value)
        present = np.asarray(state.present)
        self.rows = {row: {f"k{s}": int(values[row, s])
                           for s in range(present.shape[1])
                           if present[row, s]} for row in self.pick}
        self.serving = None

    def data(self) -> dict:
        from benchmark import kernel_bytes

        k = self.traffic["k"]
        ops = self.window_ticks * self.num_docs * k
        return {
            "window_s": self.window_s, "ops": ops,
            "attempted": self.window_ticks * self.num_docs,
            "failed": self.unacked,
            "rate_thirds": self._thirds(),
            "latencies_ms": [], "lateness_ms": [], "ledger": [],
            "counters": {}, "host": {"tick_s": list(self.tick_s)},
            "kernels": {"map_fold": "_storm_tick"},
            "kernel_bytes": {"map_fold": kernel_bytes.map_fold_bytes(
                self.sizes["docs_per_chip"], k, self.sizes["key_slots"])},
        }

    def _thirds(self) -> dict:
        """Ops per second over the first, middle and last third of the
        window's ticks."""
        n = len(self.wall_s)
        if n < 3:
            return {}
        cut = [0, n // 3, n - n // 3, n]
        per_tick = self.num_docs * self.traffic["k"]
        parts = [slice(cut[i], cut[i + 1]) for i in range(3)]
        return {"ops_per_s": [per_tick * len(self.wall_s[p])
                              / sum(self.wall_s[p]) for p in parts]}

    def _batches(self, row: int) -> list:
        nb = len(self.bodies)
        return [self.bodies[t % nb][row] for t in range(self.ticks)]

    def check(self) -> dict:
        bad = sum(ref_map.lww_fold(self._batches(row)) != got
                  for row, got in self.rows.items())
        return {"unacked_rows": self.unacked, "map_bad_rows": bad,
                "chips_without_state": len(self.devices)
                - self.shard_devices}

    def use_control(self) -> None:
        """The control in the program's place: the reference's map rows
        with per-doc total order broken (each row's last two batches
        folded in swapped order)."""
        self.rows = {row: ref_map.lww_fold(ref_map.swap_last(
            self._batches(row))) for row in self.rows}

    def close(self) -> None:
        self.serving = None
