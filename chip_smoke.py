"""Bring-up smoke on the chip: drive the served path once through its own
entry points, check every result against the repo's scalar engines, and
print one JSON line per phase, then the result line.

    python chip_smoke.py               # one chip: map storm, text, all-family
    python chip_smoke.py --multichip   # four chips: the docs-sharded tick only

Phases (one process holds the chip; the storm load clients are plain
scripts that never import JAX):

* ``map_storm`` — BASELINE config 3 (SharedMap op storm, 10k docs x 4
  clients, set/delete flood) at the e2e storm shape: 10,240 docs, k=512
  ops per doc per tick. Storm frames go over TCP to the C++ bridge front
  door and the StormController with the group-commit WAL. Every frame is
  acked, nothing is shed, the durable WAL covers the acked ops, and
  sampled docs equal a scalar MapData replay of ``get_deltas``.
* ``text`` — BASELINE config 2 width: 128 writers per doc on the
  block-structured table through KernelMergeHost (4,096 docs, k=32, 512
  slots), converged text equal to the scalar MergeEngine.
* ``all_family`` — the fused map + text + matrix + tree tick
  (``storm._mixed_tick``) at 8,192 docs through ShardedServing, sampled
  rows of each family equal to its scalar engine.
* ``multichip`` (``--multichip`` only) — ShardedServing's docs-sharded
  tick over 4 devices at 4 x 10,240 docs: shards on 4 distinct devices,
  sampled map rows equal to the scalar replay.

Any failed check or exception exits nonzero without the result line.
"""

from __future__ import annotations

import argparse
import json
import os
import random
import subprocess
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent


def _line(phase: str, dev, **fields) -> None:
    print(json.dumps({"phase": phase, "platform": dev.platform,
                      "device_kind": dev.device_kind, **fields}),
          flush=True)


def _check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


def _has_kernel(jitted, *args) -> bool:
    """Whether the program ``jitted`` compiles for ``args`` holds a Pallas
    kernel (a Mosaic ``tpu_custom_call``), not an XLA fallback."""
    import jax

    shapes = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), args)
    return "tpu_custom_call" in jitted.lower(*shapes).compile().as_text()


def _scalar_map(words) -> dict:
    """The storm words' LWW fold through the scalar MapData engine."""
    from fluidframework_tpu.dds.map_data import MapData

    data = MapData()
    for w in words:
        kind, slot, value = w & 3, (w >> 2) & 0x3FF, (w >> 12) & 0xFFFFF
        if kind == 0:
            op = {"type": "set", "key": f"k{slot}", "value": int(value)}
        elif kind == 1:
            op = {"type": "delete", "key": f"k{slot}"}
        else:
            op = {"type": "clear"}
        data.process(op, False, None)
    return dict(data.items())


# -- phase 1: the map op storm through the front door --------------------------


def phase_map_storm(dev, seed: int, num_docs: int = 10_240,
                    clients_per_doc: int = 4, k: int = 512, ticks: int = 6,
                    n_conns: int = 4, num_slots: int = 32,
                    sample: int = 64) -> None:
    import struct
    import tempfile

    import numpy as np

    from fluidframework_tpu.dds.map_data import MapData
    from fluidframework_tpu.native.fanout import make_fanout
    from fluidframework_tpu.ops import map_pallas as mp
    from fluidframework_tpu.protocol.messages import MessageType
    from fluidframework_tpu.server.bridge_host import BridgeFrontDoor
    from fluidframework_tpu.server.kernel_host import KernelSequencerHost
    from fluidframework_tpu.server.merge_host import KernelMergeHost
    from fluidframework_tpu.server.routerlicious import RouterliciousService
    from fluidframework_tpu.server.storm import StormController, _storm_tick

    spill = tempfile.mkdtemp(prefix="chip-smoke-wal-")
    seq_host = KernelSequencerHost(num_slots=clients_per_doc,
                                   initial_capacity=num_docs)
    merge_host = KernelMergeHost(map_slots=num_slots, row_capacity=num_docs,
                                 flush_threshold=10**9)
    service = RouterliciousService(merge_host=merge_host,
                                   batched_deli_host=seq_host,
                                   auto_pump=False, fanout=make_fanout())
    storm = StormController(service, seq_host, merge_host,
                            flush_threshold_docs=num_docs,
                            max_key_slots=num_slots, spill_dir=spill,
                            durability="group")
    front = BridgeFrontDoor(service, 0)
    procs: list[subprocess.Popen] = []
    try:
        t0 = time.perf_counter()
        docs = [f"storm-doc-{i}" for i in range(num_docs)]
        clients = {d: [service.connect(d, lambda msgs: None).client_id
                       for _ in range(clients_per_doc)] for d in docs}
        service.pump()
        setup_s = time.perf_counter() - t0

        # Warm-up: one full-shape tick in process (client 0 of every
        # doc) compiles the fused deli + map-fold program.
        rng = np.random.default_rng(seed)
        words0 = (rng.integers(0, 1 << 20, (num_docs, k)).astype(np.uint32)
                  << 12)
        t0 = time.perf_counter()
        storm.submit_frame(None, {"op": "storm", "docs": [
            [d, clients[d][0], 1, 1, k] for d in docs]},
            memoryview(words0.tobytes()))
        storm.flush()
        compile_s = time.perf_counter() - t0
        _check(storm.stats["sequenced_ops"] == num_docs * k,
               f"warm-up sequenced {storm.stats['sequenced_ops']}")
        ticks_before = storm.stats["ticks"]

        per_conn = num_docs // n_conns
        client_py = REPO / "fluidframework_tpu" / "tools" / "storm_client.py"
        for c in range(n_conns):
            conn_docs = docs[c * per_conn:(c + 1) * per_conn]
            proc = subprocess.Popen(
                [sys.executable, str(client_py)], stdin=subprocess.PIPE,
                stdout=subprocess.PIPE, text=True,
                env={**os.environ, "JAX_PLATFORMS": "cpu"})
            procs.append(proc)
            proc.stdin.write(json.dumps({
                "port": front.port, "k": k, "ticks": ticks,
                "seed": seed * 100 + c, "num_slots": num_slots,
                "kinds": [0, 0, 0, 1], "window": 2,
                "docs": [[d, clients[d]] for d in conn_docs],
                "cseq0": [[k + 1] + [1] * (clients_per_doc - 1)]
                * len(conn_docs)}) + "\n")
            proc.stdin.flush()
        for proc in procs:
            _check(proc.stdout.readline().strip() == "READY",
                   "storm client did not start")
        storm.ledger.clear()  # tick walls count from the first frame
        t0 = time.perf_counter()
        for proc in procs:
            proc.stdin.write("GO\n")
            proc.stdin.flush()
        results = [json.loads(proc.stdout.readline()) for proc in procs]
        elapsed = time.perf_counter() - t0
        for proc in procs:
            _check(proc.wait(timeout=60) == 0, "storm client failed")
        storm.flush()

        _check(not any(r["jax_imported"] for r in results),
               "a storm client imported JAX")
        acked = sum(len(r["ack_times"]) for r in results)
        _check(acked == n_conns * ticks, f"acked {acked} frames")
        _check(sum(r["nacked"] for r in results) == 0, "frames were nacked")
        _check(storm.stats["shed_frames"] == 0, "frames were shed")
        steady = storm.stats["ticks"] - ticks_before
        _check(steady >= 4, f"only {steady} steady ticks")
        acked_ops = num_docs * k * (1 + ticks)  # warm-up + acked frames
        wal = storm._group_wal
        _check(wal.durable_len == storm.stats["ticks"],
               f"durable {wal.durable_len} of {storm.stats['ticks']} ticks")
        durable_ops = 0
        for i in range(wal.durable_len):
            rec = wal.read(i)
            hlen = struct.unpack_from("<I", rec)[0]
            hdr = json.loads(rec[4:4 + hlen])
            durable_ops += sum(entry[5] for entry in hdr["docs"])
        _check(durable_ops >= acked_ops,
               f"WAL holds {durable_ops} ops, {acked_ops} acked")
        _check(storm.stats["sequenced_ops"] == acked_ops,
               f"sequenced {storm.stats['sequenced_ops']} of {acked_ops}")

        pick = random.Random(seed).sample(docs, sample)
        for d in pick:
            data = MapData()
            for m in service.get_deltas(d, 0):
                if m.type != MessageType.OPERATION:
                    continue
                inner = (m.contents or {}).get("contents",
                                               {}).get("contents")
                if inner:
                    data.process(inner, False, None)
            _check(merge_host.map_entries(d, "default", "root")
                   == dict(data.items()), f"{d} differs from MapData")

        _check(not mp.default_interpret(), "map fold in interpret mode")
        b_seq, b_map = seq_host._capacity, merge_host._map_capacity
        vec_seq = np.zeros(b_seq, np.int32)
        vec_map = np.zeros(b_map, np.int32)
        _check(_has_kernel(_storm_tick, seq_host._state, merge_host._xstate,
                           vec_seq, vec_seq, vec_seq, vec_seq, vec_seq,
                           vec_map, np.zeros((b_map, k), np.uint32),
                           vec_map), "storm tick holds no Pallas kernel")
        tick_ms = sorted(r["wall"] / 1e6 for r in storm.ledger.records())
        _line("map_storm", dev, ok=True,
              shapes={"docs": num_docs, "clients_per_doc": clients_per_doc,
                      "k": k, "key_slots": num_slots, "conns": n_conns},
              setup_s=setup_s, compile_s=compile_s, steady_ticks=steady,
              steady_s=elapsed,
              ops_per_s=num_docs * k * ticks / elapsed,
              tick_ms_p50=tick_ms[len(tick_ms) // 2],
              tick_ms_max=tick_ms[-1], durable_ticks=wal.durable_len,
              sampled_docs=sample, kernel="pallas_map_fold")
    finally:
        for proc in procs:
            if proc.poll() is None:
                proc.kill()
                proc.wait()
        front.close()
        if storm._group_wal is not None:
            storm._group_wal.close()
        import shutil
        shutil.rmtree(spill, ignore_errors=True)


# -- phase 2: 128 writers per text doc on the block table ----------------------


def _text_stream(rng: random.Random, n_ops: int, writers: int,
                 max_ref_lag: int = 4) -> tuple[list, str]:
    """((op, seq, ref_seq, writer) list, converged text): concurrent refs,
    each position valid in its (ref, writer) view, generated against the
    scalar MergeEngine that is also the reference. Writer w's first op
    lands in tick w // 32 so the remover bitmask widens early."""
    from fluidframework_tpu.dds.mergetree import MergeEngine

    engine = MergeEngine()
    out = []
    for seq in range(1, n_ops + 1):
        writer = (seq - 1) if seq <= writers else rng.randrange(writers)
        ref = rng.randrange(max(seq - max_ref_lag, 0), seq)
        length = len(engine.get_text(ref, f"w{writer}"))
        if length > 8 and rng.random() < 0.35:
            start = rng.randrange(length - 4)
            op = {"type": "remove", "start": start,
                  "end": start + rng.randint(1, 4)}
        else:
            op = {"type": "insert", "pos": rng.randint(0, length),
                  "text": "".join(rng.choice("abcdefgh")
                                  for _ in range(rng.randint(1, 4)))}
        engine.apply_remote(op, seq, ref, f"w{writer}")
        engine.update_min_seq(max(0, seq - 5))
        out.append((op, seq, ref, writer))
    return out, engine.get_text()


def phase_text(dev, seed: int, num_docs: int = 4096, writers: int = 128,
               k: int = 32, ticks: int = 7, slots: int = 512,
               variants: int = 8, sample: int = 64) -> None:
    import numpy as np

    from fluidframework_tpu.ops import mergetree_blocks_pallas as mtbp
    from fluidframework_tpu.ops import mergetree_kernel as mtk
    from fluidframework_tpu.protocol.messages import (
        MessageType, SequencedDocumentMessage)
    from fluidframework_tpu.server.merge_host import (
        KernelMergeHost, _BlockMergePool)

    rng = random.Random(seed)
    streams, expected = zip(*(_text_stream(rng, k * ticks, writers)
                              for _ in range(variants)))
    host = KernelMergeHost(merge_slots=slots, row_capacity=num_docs,
                           flush_threshold=10**9, max_client_slots=writers)
    docs = [f"text-doc-{i}" for i in range(num_docs)]
    tick_s = []
    for t in range(ticks):
        for i, d in enumerate(docs):
            for op, seq, ref, w in streams[i % variants][t * k:(t + 1) * k]:
                host.ingest(d, SequencedDocumentMessage(
                    client_id=f"w{w}", sequence_number=seq,
                    minimum_sequence_number=max(0, seq - 5),
                    client_sequence_number=seq,
                    reference_sequence_number=ref,
                    type=MessageType.OPERATION,
                    contents={"address": "default", "contents": {
                        "address": "text", "contents": op}}))
        t0 = time.perf_counter()
        host.flush()
        for pool in host._merge_pools.values():
            pool.state.count.block_until_ready()
        tick_s.append(time.perf_counter() - t0)

    for i in random.Random(seed).sample(range(num_docs), sample):
        _check(host.text(docs[i], "default", "text")
               == expected[i % variants], f"{docs[i]} text differs")

    _check(host.stats["scalar_ops"] == 0, "text rows fell back to scalar")
    pools = list(host._merge_pools.values())
    _check(all(isinstance(p, _BlockMergePool) for p in pools),
           f"pools {[type(p).__name__ for p in pools]}")
    pool = max(pools, key=lambda p: p.slots)
    _check(pool.client_capacity >= writers, "overlap planes too narrow")
    _check(not mtbp.default_interpret(), "block kernel in interpret mode")
    batch = mtk.MergeOpBatch(**{
        name: np.zeros((pool.capacity, k), bool if name == "valid"
                       else np.int32) for name in mtk.MergeOpBatch._fields})
    _check(_has_kernel(mtbp.apply_tick_blocks_pallas, pool.state, batch),
           "block tick holds no Pallas kernel")
    _line("text", dev, ok=True,
          shapes={"docs": num_docs, "writers": writers, "k": k,
                  "slots": pool.nb * pool.bk, "block_geometry":
                  [pool.nb, pool.bk], "overlap_words": pool.overlap_words},
          compile_s=tick_s[0], steady_ticks=len(tick_s) - 3,
          steady_tick_ms=[1000.0 * s for s in tick_s[3:]],
          sampled_docs=sample, kernel="pallas_block_mergetree",
          device_ops=host.stats["device_ops"])


# -- phase 3: the fused all-family tick ----------------------------------------


def phase_all_family(dev, seed: int, num_docs: int = 8192, ticks: int = 4,
                     map_k: int = 64, text_k: int = 16, matrix_k: int = 16,
                     tree_k: int = 8, sample: int = 8) -> None:
    import jax
    import numpy as np

    from fluidframework_tpu.dds.matrix import PermutationVector
    from fluidframework_tpu.dds.mergetree import MergeEngine
    from fluidframework_tpu.dds.tree_core import (
        ROOT_ID, VALID, Transaction, TreeSnapshot)
    from fluidframework_tpu.ops import matrix_kernel as mxk
    from fluidframework_tpu.ops import mergetree_kernel as mtk
    from fluidframework_tpu.ops import tree_kernel as tk
    from fluidframework_tpu.parallel.mesh import make_mesh
    from fluidframework_tpu.parallel.serving import HostPort, ShardedServing
    from fluidframework_tpu.parallel.serving import _plane_rows

    families = ("map", "text", "matrix", "tree")
    serving = ShardedServing(
        make_mesh(jax.devices()[:1]), num_docs=num_docs, k=map_k,
        num_hosts=1, num_clients=2, map_slots=32,
        text_slots=2 * text_k * ticks + 64, text_k=text_k,
        matrix_vec_slots=4 * ticks + 16, matrix_handles=2 * ticks + 16,
        matrix_k=matrix_k, tree_slots=2 * tree_k, tree_k=tree_k)
    serving.join_all()
    rng = np.random.default_rng(seed)

    def text_ops(t):
        ops = [dict(kind=mtk.MT_INSERT, pos=i, text="xy"[i % 2] * (i + 1))
               for i in range(text_k - 4)]
        return ops + [dict(kind=mtk.MT_REMOVE, pos=2 * i, end=2 * i + 1)
                      for i in range(4)]

    def matrix_ops(t):
        ops = [dict(target=mxk.MX_ROWS, kind=mtk.MT_INSERT, pos=0, count=1),
               dict(target=mxk.MX_COLS, kind=mtk.MT_INSERT, pos=0, count=1)]
        return ops + [dict(target=mxk.MX_CELL,
                           row=int(rng.integers(0, t + 1)),
                           col=int(rng.integers(0, t + 1)),
                           value=int(rng.integers(1, 1 << 16)))
                      for _ in range(matrix_k - 2)]

    def tree_ops(t):
        if t == 0:
            return [dict(kind=tk.TREE_INSERT, node=i + 1, parent=0,
                         trait=1, payload=i) for i in range(tree_k)]
        return [dict(kind=tk.TREE_SET_VALUE, node=i + 1,
                     payload=t * 100 + i) for i in range(tree_k)]

    # Every row of a non-map family sees its family's scripted traffic;
    # map rows get their own random words.
    cseq = [1] * num_docs
    ref = [1] * num_docs  # the doc seq after the one join
    log: dict[str, list] = {f: [] for f in families}  # (ops, seq0, ref)
    map_log: dict[int, list] = {}
    tick_s = []
    for t in range(ticks):
        scripted = {"text": text_ops(t), "matrix": matrix_ops(t),
                    "tree": tree_ops(t)}
        first_row = {}
        for row in range(num_docs):
            fam = families[row % 4]
            if fam == "map":
                words = ((rng.integers(0, 4, map_k) % 3).astype(np.uint32)
                         | rng.integers(0, 32, map_k).astype(np.uint32) << 2
                         | rng.integers(0, 1 << 20, map_k).astype(
                             np.uint32) << 12)
                serving.submit(row, words, cseq[row], ref[row])
                map_log.setdefault(row, []).append(words)
                n = map_k
            else:
                ops = scripted[fam]
                getattr(serving, "submit_" + fam)(row, ops, cseq[row],
                                                  ref[row])
                n = len(ops)
                first_row.setdefault(fam, (ops, ref[row]))
            cseq[row] += n
        t0 = time.perf_counter()
        harvest = serving.tick()
        harvest = serving.flush() or [harvest]
        tick_s.append(time.perf_counter() - t0)
        acks = {}
        for per_host in harvest:
            for rows in per_host.values():
                acks.update(rows)
        for row in range(num_docs):
            n_ok, first, last = acks[row]
            fam = families[row % 4]
            n = map_k if fam == "map" else len(scripted[fam])
            _check(n_ok == n and first == ref[row] + 1,
                   f"row {row} tick {t}: ack {acks[row]}")
            ref[row] = last
        for fam, (ops, r) in first_row.items():
            log[fam].append((ops, r + 1, r))

    rows_of = {f: [r for r in range(num_docs) if families[r % 4] == f]
               for f in families}
    pick = random.Random(seed)
    # map: MapData over each sampled row's words
    values = np.asarray(serving.map_state.value)
    present = np.asarray(serving.map_state.present)
    for row in pick.sample(rows_of["map"], sample):
        want = _scalar_map(np.concatenate(map_log[row]).tolist())
        got = {f"k{s}": int(values[row, s])
               for s in range(present.shape[1]) if present[row, s]}
        _check(got == want, f"map row {row} differs")
    # text: MergeEngine with the ticket's seqs
    engine = MergeEngine()
    for ops, seq0, r in log["text"]:
        for i, op in enumerate(ops):
            if op["kind"] == mtk.MT_INSERT:
                sop = {"type": "insert", "pos": op["pos"], "text": op["text"]}
            else:
                sop = {"type": "remove", "start": op["pos"],
                       "end": op["end"]}
            engine.apply_remote(sop, seq0 + i, r, "c0")
    for row in pick.sample(rows_of["text"], sample):
        _check(serving.text_of(row) == engine.get_text(),
               f"text row {row} differs")
    # matrix: two PermutationVectors + the LWW cell dict
    rows_vec, cols_vec, cells = (PermutationVector(None),
                                 PermutationVector(None), {})
    for ops, seq0, r in log["matrix"]:
        for i, op in enumerate(ops):
            if op["target"] == mxk.MX_CELL:
                rh = rows_vec.handle_at(op["row"], r, "c0")
                ch = cols_vec.handle_at(op["col"], r, "c0")
                if rh is not None and ch is not None:
                    cells[(rh, ch)] = op["value"]
            else:
                vec = rows_vec if op["target"] == mxk.MX_ROWS else cols_vec
                vec.apply_remote({"type": "insert", "pos": op["pos"],
                                  "count": op["count"]}, seq0 + i, r, "c0")
    live = lambda vec: [h for seg in vec.engine.segments
                        if seg.removed_seq is None for h in seg.content]
    want_grid = [[cells.get((rh, ch)) for ch in live(cols_vec)]
                 for rh in live(rows_vec)]
    for row in pick.sample(rows_of["matrix"], sample):
        one = jax.tree.map(lambda a: _plane_rows(a, HostPort(-1, row,
                                                             row + 1)),
                           serving.matrix_state)
        _check(mxk.materialize_grid(one, 0, range(1 << 16)) == want_grid,
               f"matrix row {row} differs")
    # tree: scalar Transactions
    names = {0: ROOT_ID, **{i: f"s{i}" for i in range(1, 2 * tree_k)}}
    snap = TreeSnapshot()
    for ops, _seq0, _r in log["tree"]:
        for j, op in enumerate(ops):
            name = names[op["node"]]
            if op["kind"] == tk.TREE_SET_VALUE:
                changes = [{"type": "set_value", "node": name,
                            "payload": op["payload"]}]
            else:
                changes = [
                    {"type": "build", "source": [{
                        "id": name, "definition": "n",
                        "payload": op["payload"]}],
                     "destination": f"b{j}"},
                    {"type": "insert", "source": f"b{j}", "destination": {
                        "referenceTrait": {"parent": names[op["parent"]],
                                           "label": f"t{op['trait']}"},
                        "side": "end"}}]
            txn = Transaction(snap)
            _check(txn.apply_edit({"id": "e", "changes": changes}) == VALID,
                   "scalar tree edit refused")
            snap = txn.snapshot
    want_order = snap.get(ROOT_ID).traits["t1"]
    for row in pick.sample(rows_of["tree"], sample):
        one = jax.tree.map(lambda a: _plane_rows(a, HostPort(-1, row,
                                                             row + 1)),
                           serving.tree_state)
        order = [names[s] for s in tk.trait_order(one, 0, 0, 1)]
        payload = np.asarray(one.payload[0])
        _check(order == want_order, f"tree row {row} order differs")
        _check(all(int(payload[s]) == snap.get(names[s]).payload
                   for s in tk.trait_order(one, 0, 0, 1)),
               f"tree row {row} payload differs")

    _line("all_family", dev, ok=True,
          shapes={"docs": num_docs, "per_family": num_docs // 4,
                  "k": {"map": map_k, "text": text_k, "matrix": matrix_k,
                        "tree": tree_k}},
          compile_s=tick_s[0], steady_ticks=len(tick_s) - 1,
          steady_tick_ms=[1000.0 * s for s in tick_s[1:]],
          sampled_rows_per_family=sample)


# -- four chips: the docs-sharded serving tick ---------------------------------


def phase_multichip(dev, seed: int, per_device: int = 10_240, k: int = 512,
                    ticks: int = 4, n_devices: int = 4,
                    sample: int = 256) -> None:
    import jax
    import numpy as np

    from fluidframework_tpu.ops import map_pallas as mp
    from fluidframework_tpu.parallel.mesh import make_mesh
    from fluidframework_tpu.parallel.serving import ShardedServing

    devices = jax.devices()
    _check(len(devices) >= n_devices, f"{len(devices)} devices")
    num_docs = per_device * n_devices
    serving = ShardedServing(make_mesh(devices[:n_devices]),
                             num_docs=num_docs, k=k, num_hosts=n_devices,
                             num_clients=2, map_slots=32)
    serving.join_all()
    rng = np.random.default_rng(seed)
    pick = sorted(random.Random(seed).sample(range(num_docs), sample))
    logs = {row: [] for row in pick}
    cseq, ref = 1, 1
    tick_s = []
    for t in range(ticks):
        words = ((rng.integers(0, 4, (num_docs, k)) % 3).astype(np.uint32)
                 | rng.integers(0, 32, (num_docs, k)).astype(np.uint32) << 2
                 | rng.integers(0, 1 << 20, (num_docs, k)).astype(
                     np.uint32) << 12)
        for row in range(num_docs):
            serving.submit(row, words[row], cseq, ref)
        for row in pick:
            logs[row].append(words[row])
        t0 = time.perf_counter()
        harvest = serving.tick()
        harvest = serving.flush() or [harvest]
        tick_s.append(time.perf_counter() - t0)
        acks = {}
        for per_host in harvest:
            for rows in per_host.values():
                acks.update(rows)
        _check(len(acks) == num_docs
               and all(a[0] == k for a in acks.values()),
               f"tick {t}: {len(acks)} rows acked")
        cseq += k
        ref = 1 + (t + 1) * k

    leaf = serving.map_state.value
    shard_devices = {s.device for s in leaf.addressable_shards}
    _check(len(shard_devices) == n_devices,
           f"map state on {len(shard_devices)} devices")
    _check(not mp.default_interpret(), "map fold in interpret mode")
    values = np.asarray(serving.map_state.value)
    present = np.asarray(serving.map_state.present)
    for row in pick:
        want = _scalar_map(np.concatenate(logs[row]).tolist())
        got = {f"k{s}": int(values[row, s])
               for s in range(present.shape[1]) if present[row, s]}
        _check(got == want, f"map row {row} differs from the scalar replay")
    _line("multichip", dev, ok=True,
          shapes={"docs": num_docs, "devices": n_devices, "k": k},
          shard_devices=sorted(str(d) for d in shard_devices),
          compile_s=tick_s[0], steady_ticks=len(tick_s) - 1,
          steady_tick_ms=[1000.0 * s for s in tick_s[1:]],
          sampled_docs=sample)


def _require_native() -> None:
    """The served path needs the C++ bridge, fan-out and oplog: no silent
    fallback to the Python twins here."""
    from fluidframework_tpu import native
    from fluidframework_tpu.native import _loader, bridge, fanout

    for name, load in (("bridge", bridge._load_library),
                       ("fanout", fanout._load_library),
                       ("oplog", native._load_library)):
        if load() is None:
            raise RuntimeError(f"native {name} did not build: "
                               f"{_loader.build_errors.get(name)}")


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--multichip", action="store_true",
                        help="run only the 4-chip docs-sharded phase")
    parser.add_argument("--seed", type=int, default=0)
    args = parser.parse_args(argv)

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev.platform})",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(REPO))
    from fluidframework_tpu.utils import compile_cache

    cache = compile_cache.enable()
    _line("setup", dev, ok=True, compile_cache_dir=cache,
          jax=jax.__version__, device_count=len(jax.devices()))
    if args.multichip:
        phase_multichip(dev, args.seed)
    else:
        _require_native()
        phase_map_storm(dev, args.seed)
        phase_text(dev, args.seed)
        phase_all_family(dev, args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
