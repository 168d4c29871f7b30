"""On-chip smoke of the serving paths that no benchmark cell runs yet.

Each phase drives its path once through its own entry points, checks
every result against the repo's scalar engines and prints one JSON line;
the result line comes last.

    python chip_smoke.py               # one chip: text, all-family

Phases (one process holds the chip):

* ``text`` — BASELINE config 2 width: 128 writers per doc on the
  block-structured table through KernelMergeHost (4,096 docs, k=32, 512
  slots), converged text equal to the scalar MergeEngine.
* ``all_family`` — the fused map + text + matrix + tree tick
  (``storm._mixed_tick``) at 8,192 docs through ShardedServing, sampled
  rows of each family equal to its scalar engine.

The map storm through the front door and the docs-sharded tick over four
chips are benchmark cells (``map_storm_10k.flood`` and
``map_sharded_4chip.flood`` in ``BENCHMARK.json``), each checked against
its own plain reference there.

Any failed check or exception exits nonzero without the result line.
"""

from __future__ import annotations

import argparse
import json
import random
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


# -- phase 1: text on the block merge-tree ---------------------------------------


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


# -- phase 2: the fused all-family tick ----------------------------------------


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
        tick_s.append(time.perf_counter() - t0)
        acks = {}
        for rows in harvest.values():
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


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
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
    phase_text(dev, args.seed)
    phase_all_family(dev, args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
