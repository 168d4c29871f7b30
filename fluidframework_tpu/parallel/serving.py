"""Sharded serving assembly — the multi-host deployment of the storm
pipeline (SURVEY §5.8, the partitionManager.ts scale-out analog).

The reference scales its ordering service by Kafka partitions assigning
documents to consumer PROCESSES
(server/routerlicious/packages/lambdas-driver/src/kafka-service/
partitionManager.ts:24; config.json numberOfPartitions). Here the same
assignment is the document axis of a ``jax.sharding.Mesh``:

* each serving host (process) owns a CONTIGUOUS document-row range — in
  a real multi-host deployment that range is
  :func:`..parallel.multihost.local_docs`; the front door / bus routes
  exactly those documents to it (the partition-assignment analog);
* every host contributes its rows' columnar op planes; the global
  [B, K] arrays are mesh-sharded so no host materializes another's rows
  on its devices;
* ONE fused device program — the same deli+merger tick the
  single-process storm path runs (server/storm.py ``_storm_tick`` /
  ``_mixed_tick``) — executes SPMD over the mesh; outputs stay sharded;
* each host harvests ONLY its own rows (addressable shards) for acks,
  durability and broadcast.

ALL op families ride the one tick (the reference's single deltas
stream — deli/lambda.ts:82 tickets every op type, scriptorium
lambda.ts:16 consumes them uniformly): a document row can carry a map
channel (packed u32 words), a merge-tree text channel, a matrix channel
or a tree channel; the fused program tickets every row's batch with the
closed-form deli and applies each family's windowed ops in the same
XLA program, sharded over the mesh.

Single-process deployments (and the virtual-CPU-mesh dryrun) run the
identical code with simulated hosts: the per-host routing, sharded tick
and shard-local harvest are exactly what a multi-process launch runs,
with :func:`..parallel.multihost.feed` as the only difference in how the
global arrays assemble.
"""

from __future__ import annotations

from typing import Any, NamedTuple

import jax
import numpy as np

from ..ops import map_kernel as mk
from ..ops import matrix_kernel as mxk
from ..ops import mergetree_blocks as mtb
from ..ops import mergetree_kernel as mtk
from ..ops import sequencer as seqk
from ..ops import tree_kernel as tk
from ..protocol.messages import MessageType
from ..utils import StageLedger, faults
from . import multihost
from .mesh import aggregate_metrics

TEXT_FIELDS = ("kind", "pos", "end", "ref_seq", "client",
               "pool_start", "text_len", "prop_key", "prop_val")
MATRIX_FIELDS = ("target", "kind", "pos", "end", "count", "handle_base",
                 "row", "col", "value", "ref_seq", "client")
TREE_FIELDS = ("kind", "node", "parent", "trait", "payload")

#: Stage order of one sharded tick, the fixed shape of its ledger records:
#: ``admit`` runs from the first submit after a tick to ``tick()``,
#: ``assemble`` builds the host buffers and durable records, ``feed``
#: places them on the mesh, ``dispatch`` calls the fused program and
#: starts the readback copies, ``readback`` reads the addressable rows,
#: ``record`` appends the durable records.
SHARD_STAGES = ("admit", "assemble", "feed", "dispatch", "readback",
                "record")


def _plane_rows(arr, port: "HostPort") -> np.ndarray:
    """Host copy of one state plane's rows in [start, stop) — assembled
    from addressable shards only. A checkpoint must cover the WHOLE
    range: rows resident on another process's devices cannot be silently
    zero-filled (restoring zeroed sequencer counters would regress
    sequence numbers), so partial coverage raises — each process
    checkpoints its own range."""
    lead = port.stop - port.start
    out = None
    covered = 0
    for shard in arr.addressable_shards:
        row_slice = shard.index[0]
        lo = row_slice.start if row_slice.start is not None else 0
        data = np.asarray(shard.data)
        hi = lo + data.shape[0]
        s, e = max(lo, port.start), min(hi, port.stop)
        if s >= e:
            continue
        if out is None:
            out = np.zeros((lead,) + data.shape[1:], data.dtype)
        out[s - port.start:e - port.start] = data[s - lo:e - lo]
        covered += e - s
    if out is None or covered < lead:
        raise ValueError(
            f"host range [{port.start}, {port.stop}) only has {covered} "
            "addressable rows on this process; checkpoint each process's "
            "own range")
    return out


def _addressable_rows(arr) -> dict[int, int]:
    """row -> value from the shards THIS process can address (never the
    global array: in a multi-process mesh it spans foreign devices)."""
    out: dict[int, int] = {}
    for shard in arr.addressable_shards:
        row_slice = shard.index[0]
        start = row_slice.start if row_slice.start is not None else 0
        for offset, value in enumerate(np.asarray(shard.data)):
            out[start + offset] = int(value)
    return out


class HostPort(NamedTuple):
    """One serving host's front door: the doc-row range it owns and the
    columnar buffers its connections fill (the bus-partition analog)."""

    host_id: int
    start: int
    stop: int

    def owns(self, row: int) -> bool:
        return self.start <= row < self.stop


class _Sub(NamedTuple):
    """One admitted per-row submission awaiting the tick (and, after it,
    the payload of the row's durable record — the replay source)."""

    family: str        # "map" | "text" | "matrix" | "tree"
    planes: Any        # words u32[n] (map) or {field: i32[n]} planes
    count: int
    cseq0: int
    ref: int
    client: int        # sequencer client slot
    text: str          # inserted text blob (text family)
    pool_base: int     # row pool length before this submission's append


class ShardedServing:
    """N serving hosts over one docs-sharded mesh, running the fused
    sequencer + all-family storm tick as a single SPMD program.

    Every document row has a sequencer lane set; rows carrying map
    channels use the packed-word :meth:`submit`, text rows
    :meth:`submit_text`, matrix rows :meth:`submit_matrix`, tree rows
    :meth:`submit_tree` — one submission per row per tick (per-doc total
    order), all families sequenced and applied by ONE device program.

    Failure story (kafka-service/checkpointManager.ts:24 analog): every
    tick appends one durable columnar record per submitted row to
    ``durable`` (the scriptorium leg); :meth:`checkpoint_host` captures a
    host's row states + per-row log offsets. When a host dies, its device
    state dies with it — a replacement assembly (possibly with its doc
    range REASSIGNED to surviving hosts, :meth:`rebalance_from`) restores
    the checkpoints and replays the durable tail through the REAL tick
    path; the sequencer's clientSeq dedup makes the replay idempotent and
    the restored seq counters make it regression-free."""

    def __init__(self, mesh: jax.sharding.Mesh, num_docs: int, k: int,
                 num_hosts: int, num_clients: int = 2,
                 map_slots: int = 32,
                 durable_retention_ticks: int = 1024,
                 text_slots: int = 0, text_k: int = 0, text_props: int = 4,
                 text_locality: float = 0.0,
                 matrix_vec_slots: int = 0, matrix_handles: int = 0,
                 matrix_k: int = 0,
                 tree_slots: int = 0, tree_k: int = 0) -> None:
        if num_docs % mesh.devices.size:
            raise ValueError("num_docs must divide over the mesh")
        self.mesh = mesh
        self.num_docs = num_docs
        self.k = k
        self.map_slots = map_slots
        self.num_clients = num_clients
        # The doc rows THIS PROCESS feeds and harvests. Single-process
        # (simulated hosts): the full range. Real multi-process launch:
        # this process's contiguous slice — every array below assembles
        # via multihost.feed from exactly these rows, so the same code
        # runs both shapes (tests/test_multihost.py spawns the real
        # 2-process case).
        self.local_lo, self.local_hi = multihost.local_docs(mesh, num_docs)
        # Initial states build at LOCAL size (constant fills) — a process
        # must not allocate the full global [B, ...] arrays just to slice
        # out its own rows.
        b_local = self.local_hi - self.local_lo
        lift = lambda tree: multihost.feed(
            mesh, jax.tree.map(np.asarray, tree), global_batch=num_docs)
        self.seq_state = lift(seqk.init_state(b_local, num_clients + 1))
        self.map_state = lift(mk.init_state(b_local, map_slots))
        # Optional channel families — rows share the document axis: row i
        # of every family state IS document i, so one mesh sharding (and
        # one host range) covers every family (the reference's
        # any-document-any-channel contract).
        overlap_words = mtk.overlap_words_for(num_clients)
        self.text_slots = text_slots
        self.text_k = text_k or (k if text_slots else 0)
        # Text rows live in the block-structured table (the serving
        # path, ops/mergetree_blocks.py); geometry guarantees a
        # capacity-checked tick can never overflow a block given the
        # per-tick fused rebalance inside _mixed_tick. ``text_locality``
        # is the expected head-concentration fraction (0 = the
        # historical geometry); retune_text_geometry() re-derives it
        # later from the OBSERVED rebalance fire rate (the device
        # kstats plane) and re-blocks in place.
        self.text_props = text_props
        self.text_geometry = (mtb.choose_block_geometry(
            text_slots, self.text_k, text_locality)
            if text_slots else None)
        self.merge_state = lift(mtb.init_state(
            b_local, *self.text_geometry,
            text_props, overlap_words)) if text_slots else None
        #: Cumulative mixed-tick rebalance attribution (device-true,
        #: from the kstats plane): the observed-locality input.
        self.rebalance_stats = {"ticks": 0, "fired": 0,
                                "blocks_touched": 0}
        self.matrix_vec_slots = matrix_vec_slots
        # Handle capacity of each axis: the cell planes are
        # [matrix_handles, matrix_handles] per doc (one handle counter
        # per doc serves both axes, so either axis may use all of it).
        self.matrix_handles = matrix_handles
        self.matrix_k = matrix_k or (k if matrix_vec_slots else 0)
        self.matrix_state = lift(mxk.init_state(
            b_local, matrix_vec_slots, matrix_handles,
            overlap_words=overlap_words)) if matrix_vec_slots else None
        self.tree_slots = tree_slots
        self.tree_k = tree_k or (k if tree_slots else 0)
        self.tree_state = lift(tk.init_state(
            b_local, tree_slots)) if tree_slots else None
        self._mixed = bool(text_slots or matrix_vec_slots or tree_slots)
        # Host-side text pools + capacity high-water marks for OWNED rows
        # (device overflow is silent by kernel contract, so admission
        # checks worst-case growth BEFORE the tick: 2 slots per text op,
        # 2 vector slots per matrix vector op, handles within the planes).
        local_rows = range(self.local_lo, self.local_hi)
        self.text_pool = ({row: "" for row in local_rows}
                          if text_slots else {})
        self._text_high = ({row: 0 for row in local_rows}
                           if text_slots else {})
        self._mx_high = ({row: [0, 0] for row in local_rows}
                         if matrix_vec_slots else {})  # [rows, cols]
        # ONE handle counter per doc SHARED by both axes (the
        # deterministic in-sequence-order rule of dds/matrix.py that
        # mxk.HandleAllocator mirrors).
        self._mx_handles = ({row: 0 for row in local_rows}
                            if matrix_vec_slots else {})
        # Contiguous per-host ranges — what multihost.local_docs reports
        # per process in a real multi-host launch.
        bounds = np.linspace(0, num_docs, num_hosts + 1).astype(int)
        self.hosts = [HostPort(i, int(bounds[i]), int(bounds[i + 1]))
                      for i in range(num_hosts)]
        self._pending: list[dict[int, _Sub]] = [dict()
                                                for _ in range(num_hosts)]
        # Durable columnar tick records per row (the scriptorium leg of
        # the storm pipeline): the replay source for host failover.
        # Offsets in checkpoints are ABSOLUTE record counts; trim_durable
        # retires the prefix below the fleet's checkpoint horizon so a
        # long-running assembly's log memory is bounded by the
        # checkpoint cadence, not total history.
        self.durable: dict[int, list[dict]] = {}
        self._durable_base: dict[int, int] = {}
        # Automatic retention: without it an assembly that never
        # checkpoints would grow the log with total op history (the
        # unbounded-host-memory failure mode the soak tests guard
        # against). Checkpoint within the horizon, or trim explicitly.
        self.durable_retention_ticks = max(1, durable_retention_ticks)
        #: row -> overflow count from the last tick's tree leg (rank
        #: space exhausted — the host must re-rank; tests size to avoid).
        self.last_tree_overflow: dict[int, int] = {}
        # One stage-ledger record per harvested tick (ff.shard.stage.*
        # spans on the profiler's clock); ``wall`` is the harvest-to-
        # harvest cadence. The admit stage is held open from the first
        # submit of a tick to tick() — no clock read per row.
        self.ledger = StageLedger(SHARD_STAGES, prefix="shard.stage")
        self._admit_stage = None
        self._ticks = 0

    def route(self, row: int) -> HostPort:
        """The owning host of a document row (front-door routing)."""
        for port in self.hosts:
            if port.owns(row):
                return port
        raise KeyError(row)

    # -- front door ------------------------------------------------------------

    def join_all(self, slot: int = 0, slots=None) -> None:
        """Sequence a CLIENT_JOIN on every document (through the real
        sequencer kernel, not state surgery). ``slots`` joins several
        client lanes per doc in one batch — text/matrix rows with
        multiple writers need every writer's lane active."""
        lanes = tuple(slots) if slots is not None else (slot,)
        b_local = self.local_hi - self.local_lo
        ops = seqk.make_op_batch(
            [[dict(kind=int(MessageType.CLIENT_JOIN), slot=-1, target=s,
                   timestamp=1) for s in lanes]
             for _ in range(b_local)], b_local, len(lanes))
        ops = multihost.feed(self.mesh, jax.tree.map(np.asarray, ops),
                             global_batch=self.num_docs)
        # process_batch is already jitted; wrapping it again would discard
        # the trace cache per call.
        self.seq_state, out = seqk.process_batch(self.seq_state, ops)
        jax.block_until_ready(out.kind)

    def _admit(self, row: int, sub: _Sub) -> None:
        """Common admission: ownership, one-sub-per-row-per-tick, family
        capacity bookkeeping, pool append. The replay path re-admits
        recorded subs through here so recovery is the ingest path."""
        if self._admit_stage is None:
            self._admit_stage = self.ledger.stage(
                "admit", self._ticks).__enter__()
        port = self.route(row)
        pending = self._pending[port.host_id]
        if row in pending:
            raise ValueError(f"row {row} already pending this tick")
        if sub.family == "text":
            pool = self.text_pool[row]
            if len(pool) != sub.pool_base:
                raise ValueError(
                    f"row {row}: pool length {len(pool)} != submission "
                    f"base {sub.pool_base} (durable replay out of order?)")
            high = self._text_high[row] + 2 * sub.count
            if high > self.text_slots:
                raise ValueError(
                    f"row {row}: worst-case {high} segment slots exceeds "
                    f"{self.text_slots}; run compact_text() first")
            self._text_high[row] = high
            self.text_pool[row] = pool + sub.text
        elif sub.family == "matrix":
            high = self._mx_high[row]
            planes = sub.planes
            # Pre-encoded planes (bulk path / failover replay) carry
            # their own handle_bases: advance the row's allocator past
            # them so later submit_matrix allocations never collide.
            ins = (((planes["target"] == mxk.MX_ROWS)
                    | (planes["target"] == mxk.MX_COLS))
                   & (planes["kind"] == mtk.MT_INSERT))[:sub.count]
            if ins.any():
                tops = (planes["handle_base"][:sub.count]
                        + np.maximum(planes["count"][:sub.count], 1))[ins]
                self._mx_handles[row] = max(self._mx_handles[row],
                                            int(tops.max()))
            n_row = int(np.sum((planes["target"] == mxk.MX_ROWS)[:sub.count]))
            n_col = int(np.sum((planes["target"] == mxk.MX_COLS)[:sub.count]))
            grown = [high[0] + 2 * n_row, high[1] + 2 * n_col]
            if (max(grown) > self.matrix_vec_slots
                    or self._mx_handles[row] > self.matrix_handles):
                raise ValueError(
                    f"row {row}: matrix capacity exceeded: slots {grown}, "
                    f"handles {self._mx_handles[row]} vs "
                    f"({self.matrix_vec_slots}, {self.matrix_handles})")
            self._mx_high[row] = grown
        pending[row] = sub

    def submit(self, row: int, words: np.ndarray, first_cseq: int,
               ref_seq: int = 1, client_slot: int = 0) -> None:
        """One map row's packed-word op batch into its OWNING host's
        buffer — a frame for a foreign row is a routing bug and raises
        (the bus partition would never deliver it here)."""
        if len(words) > self.k:
            raise ValueError(
                f"batch of {len(words)} ops exceeds tick width {self.k}")
        self._admit(row, _Sub("map", np.asarray(words, np.uint32),
                              len(words), first_cseq, ref_seq,
                              client_slot, "", 0))

    def submit_text(self, row: int, ops: list[dict], first_cseq: int,
                    ref_seq: int = 1, client_slot: int = 0) -> None:
        """One text row's merge-tree op batch (mtk.MT_* dicts; inserts
        carry ``text``). The owning host appends inserted text to the
        row's pool and fills pool_start/text_len; the device assigns seqs
        at the tick (ops carry NO seq — the ticket does)."""
        if self.merge_state is None:
            raise ValueError("assembly built without text_slots")
        if len(ops) > self.text_k:
            raise ValueError(f"{len(ops)} text ops exceed tick width "
                             f"{self.text_k}")
        pool_base = len(self.text_pool[row])
        blob: list[str] = []
        offset = 0
        encoded = []
        for op in ops:
            op = dict(op)
            if op.get("kind", mtk.MT_INSERT) == mtk.MT_INSERT:
                text = op.pop("text", "")
                op.setdefault("pool_start", pool_base + offset)
                op.setdefault("text_len", len(text))
                blob.append(text)
                offset += len(text)
            op.setdefault("ref_seq", ref_seq)
            op.setdefault("client", client_slot)
            encoded.append(op)
        planes = {f: np.array([op.get(f, 0) for op in encoded], np.int32)
                  for f in TEXT_FIELDS}
        self._admit(row, _Sub("text", planes, len(ops), first_cseq,
                              ref_seq, client_slot, "".join(blob),
                              pool_base))

    def submit_matrix(self, row: int, ops: list[dict], first_cseq: int,
                      ref_seq: int = 1, client_slot: int = 0) -> None:
        """One matrix row's op batch (mxk fields; vector inserts without
        ``handle_base`` draw from the row's deterministic in-sequence
        handle counter, mirroring dds/matrix.py)."""
        if self.matrix_state is None:
            raise ValueError("assembly built without matrix slots")
        if len(ops) > self.matrix_k:
            raise ValueError(f"{len(ops)} matrix ops exceed tick width "
                             f"{self.matrix_k}")
        encoded = []
        for op in ops:
            op = dict(op)
            target = op.get("target", mxk.MX_CELL)
            if (target in (mxk.MX_ROWS, mxk.MX_COLS)
                    and op.get("kind", 0) == mtk.MT_INSERT):
                # Pin the count BEFORE both consumers read it: the host
                # allocator and the encoded device plane must agree, or a
                # failover-rebuilt allocator re-issues handles.
                op.setdefault("count", 1)
                if "handle_base" not in op:
                    op["handle_base"] = self._mx_handles[row]
                    self._mx_handles[row] += op["count"]
            op.setdefault("ref_seq", ref_seq)
            op.setdefault("client", client_slot)
            encoded.append(op)
        planes = {f: np.array([op.get(f, 0) for op in encoded], np.int32)
                  for f in MATRIX_FIELDS}
        self._admit(row, _Sub("matrix", planes, len(ops), first_cseq,
                              ref_seq, client_slot, "", 0))

    def submit_tree(self, row: int, ops: list[dict], first_cseq: int,
                    ref_seq: int = 1, client_slot: int = 0) -> None:
        """One tree row's op batch (tk.TREE_* dicts; node-slot management
        is the submitter's, as in the tree channel contract)."""
        if self.tree_state is None:
            raise ValueError("assembly built without tree_slots")
        if len(ops) > self.tree_k:
            raise ValueError(f"{len(ops)} tree ops exceed tick width "
                             f"{self.tree_k}")
        planes = {f: np.array([op.get(f, 0) for op in ops], np.int32)
                  for f in TREE_FIELDS}
        self._admit(row, _Sub("tree", planes, len(ops), first_cseq,
                              ref_seq, client_slot, "", 0))

    def submit_planes(self, row: int, family: str, planes: dict,
                      count: int, first_cseq: int, ref_seq: int = 1,
                      client_slot: int = 0, text: str = "",
                      pool_base: int | None = None) -> None:
        """Pre-encoded columnar admission — the decoded-frame fast path
        (the storm-frame analog for the rich op families) and the replay
        path's re-admission hook. ``planes`` carries the family's field
        arrays (text planes use ABSOLUTE pool_starts; ``text`` is the
        blob those offsets expect appended at ``pool_base``, default the
        row pool's current length)."""
        width = {"map": self.k, "text": self.text_k,
                 "matrix": self.matrix_k, "tree": self.tree_k}[family]
        if count > width:
            raise ValueError(
                f"{count} {family} ops exceed tick width {width}")
        if pool_base is None:
            pool_base = len(self.text_pool[row]) if family == "text" else 0
        self._admit(row, _Sub(family, planes, count, first_cseq, ref_seq,
                              client_slot, text, pool_base))

    # -- the sharded tick ------------------------------------------------------

    def tick(self, now: int = 2):
        """Assemble every host's contribution, run the fused SPMD tick,
        and return each host's harvest of ITS OWN rows:
        {host_id: {row: (n_seq, first_seq, last_seq)}}."""
        from ..server import storm as storm_mod
        from ..server.storm import _mixed_tick, _storm_tick

        tick = self._ticks
        self._ticks += 1
        if self._admit_stage is not None:
            self._admit_stage.__exit__(None, None, None)
            self._admit_stage = None
        with self.ledger.stage("assemble", tick):
            b = self.num_docs
            # Host buffers build at LOCAL size (this process's doc rows) —
            # never the global [B, ...] shape — exactly like the initial
            # states: each process feeds only its multihost.local_docs slice.
            lo, hi = self.local_lo, self.local_hi
            b_local = hi - lo
            slot = np.zeros(b_local, np.int32)
            cseq0 = np.zeros(b_local, np.int32)
            ref = np.zeros(b_local, np.int32)
            seq_counts = np.zeros(b_local, np.int32)
            map_words = np.zeros((b_local, self.k), np.uint32)
            map_counts = np.zeros(b_local, np.int32)
            # One packed i32[B_local, F, K] plane stack per configured family
            # (the tick's one-transfer-per-family feed; field orders pinned
            # by storm.TEXT_PACK/MATRIX_PACK/TREE_PACK, index 0 = valid).
            pack_fields = {"text": storm_mod.TEXT_PACK,
                           "matrix": storm_mod.MATRIX_PACK,
                           "tree": storm_mod.TREE_PACK}
            widths = {"text": self.text_k, "matrix": self.matrix_k,
                      "tree": self.tree_k}
            enabled = {"text": self.merge_state is not None,
                       "matrix": self.matrix_state is not None,
                       "tree": self.tree_state is not None}
            fam_pack = {
                name: (np.zeros((b_local, len(pack_fields[name]),
                                 widths[name]), np.int32)
                       if enabled[name] else None)
                for name in pack_fields}

            submitted: list[tuple[int, int]] = []  # (host, row)
            records: dict[int, dict] = {}
            for port in self.hosts:
                for row, sub in self._pending[port.host_id].items():
                    if not lo <= row < hi:
                        raise ValueError(
                            f"row {row} outside this process's doc range "
                            f"[{lo}, {hi}) cannot be fed from here")
                    r = row - lo
                    n = sub.count
                    seq_counts[r] = n
                    cseq0[r] = sub.cseq0
                    ref[r] = sub.ref
                    slot[r] = sub.client
                    if sub.family == "map":
                        map_counts[r] = n
                        map_words[r, :n] = sub.planes
                    else:
                        pack = fam_pack[sub.family]
                        pack[r, 0, :n] = 1
                        for i, f in enumerate(pack_fields[sub.family][1:]):
                            pack[r, i + 1, :n] = sub.planes[f]
                    submitted.append((port.host_id, row))
                    rec_planes = (np.array(sub.planes, np.uint32)
                                  if sub.family == "map"
                                  else {f: p.copy()
                                        for f, p in sub.planes.items()})
                    records[row] = dict(
                        family=sub.family, planes=rec_planes,
                        count=n, cseq0=sub.cseq0, ref=sub.ref,
                        client=sub.client, text=sub.text,
                        pool_base=sub.pool_base,
                        # Back-compat alias for the map-words record shape
                        # (same object — not a second copy).
                        words=(rec_planes if sub.family == "map" else None))

        put = lambda a: multihost.feed(self.mesh, a, global_batch=b)
        tree_overflow = None
        text_overflow = None
        kstats = None
        if not self._mixed:
            with self.ledger.stage("feed", tick):
                gather = np.arange(lo, hi, dtype=np.int32)
                feed = (put(slot), put(cseq0), put(ref),
                        put(np.full(b_local, now, np.int32)), put(seq_counts),
                        put(gather), put(map_words), put(map_counts))
            with self.ledger.stage("dispatch", tick):
                # The mesh context lets the tick shard_map its Pallas legs.
                with jax.set_mesh(self.mesh):
                    (self.seq_state, self.map_state, n_seq, first, last,
                     _msn, _bad, _kstats) = _storm_tick(
                        self.seq_state, self.map_state, *feed)
        else:
            with self.ledger.stage("feed", tick):
                scalars = np.stack(
                    [slot, cseq0, ref, np.full(b_local, now, np.int32),
                     seq_counts, map_counts], axis=1)
                feed = tuple(
                    put(a) if a is not None else None
                    for a in (scalars, map_words, fam_pack["text"],
                              fam_pack["matrix"], fam_pack["tree"]))
            with self.ledger.stage("dispatch", tick):
                with jax.set_mesh(self.mesh):
                    (self.seq_state, self.map_state, self.merge_state,
                     self.matrix_state, self.tree_state, n_seq, first, last,
                     _msn, tree_overflow, text_overflow, kstats) = _mixed_tick(
                        self.seq_state, self.map_state, self.merge_state,
                        self.matrix_state, self.tree_state, *feed)
        # The device program has the batch; only now may buffers drop
        # (at-least-once: an assembly failure above must keep them).
        for port in self.hosts:
            self._pending[port.host_id] = {}
        rec = dict(submitted=submitted, records=records,
                   out=(n_seq, first, last), tree_overflow=tree_overflow,
                   text_overflow=text_overflow, kstats=kstats, tick=tick,
                   ops=int(seq_counts.sum()))
        probes = rec["out"] + tuple(
            a for a in (tree_overflow, text_overflow, kstats)
            if a is not None)
        with self.ledger.stage("dispatch", tick):
            for arr in probes:
                copy_async = getattr(arr, "copy_to_host_async", None)
                if copy_async is not None:
                    copy_async()
        return self._harvest_rec(rec)

    def flush(self) -> list[dict[int, dict[int, tuple[int, int, int]]]]:
        """Harvests of ticks still outstanding, oldest first: always
        ``[]``, since :meth:`tick` harvests its own tick and its durable
        records before it returns."""
        return []

    def _harvest_rec(self, rec: dict
                     ) -> dict[int, dict[int, tuple[int, int, int]]]:
        # Shard-local harvest: each host reads ONLY the rows resident on
        # ITS addressable devices — a multi-process launch cannot (and
        # must not) materialize the global array.
        tick = rec["tick"]
        n_seq, first, last = rec["out"]
        records = rec["records"]
        with self.ledger.stage("readback", tick):
            n_seq_l = _addressable_rows(n_seq)
            first_l = _addressable_rows(first)
            last_l = _addressable_rows(last)
        harvest: dict[int, dict[int, tuple[int, int, int]]] = {
            port.host_id: {} for port in self.hosts}
        with self.ledger.stage("record", tick):
            for host_id, row in rec["submitted"]:
                n_ok = n_seq_l[row]
                harvest[host_id][row] = ((n_ok, first_l[row], last_l[row])
                                         if n_ok > 0 else (0, 0, 0))
                # scriptorium: the durable columnar record for this (row,
                # tick) — the failover replay source.
                row_rec = records[row]
                row_rec.update(n_seq=n_ok, first=first_l[row],
                               last=last_l[row])
                log = self.durable.setdefault(row, [])
                log.append(row_rec)
                overflow = len(log) - self.durable_retention_ticks
                if overflow > 0:
                    del log[:overflow]
                    self._durable_base[row] = (
                        self._durable_base.get(row, 0) + overflow)
        with self.ledger.stage("readback", tick):
            self._check_overflow(rec)
        self.ledger.record(tick, 0, len(rec["submitted"]), rec["ops"],
                           wall_ns=None)
        return harvest

    def _check_overflow(self, rec: dict) -> None:
        """The tree, text and kstats planes of a mixed tick's readback."""
        if rec["tree_overflow"] is not None:
            self.last_tree_overflow = {
                row: n
                for row, n in _addressable_rows(
                    rec["tree_overflow"]).items() if n > 0}
            if self.last_tree_overflow:
                raise RuntimeError(
                    f"tree rank overflow on rows "
                    f"{sorted(self.last_tree_overflow)}; host re-rank "
                    "required (size tree ranks for the tick width)")
        if rec.get("kstats") is not None:
            # Rebalance attribution off the existing readback (the
            # kstats cells are replicated scalars — every process reads
            # its own copy): the observed-locality input of
            # retune_text_geometry.
            from ..server import storm as storm_mod
            ks = np.asarray(rec["kstats"])
            self.rebalance_stats["ticks"] += 1
            self.rebalance_stats["fired"] += int(
                ks[storm_mod.KSTAT_REBALANCE_FIRED])
            self.rebalance_stats["blocks_touched"] += int(
                ks[storm_mod.KSTAT_BLOCKS_TOUCHED])
        if rec.get("text_overflow") is not None:
            # choose_block_geometry + the fused per-tick rebalance make
            # this unreachable for capacity-checked admissions; a hit
            # means the geometry contract was violated — fail loudly.
            overflowed = {
                row: idx for row, idx in _addressable_rows(
                    rec["text_overflow"]).items()
                if idx != int(mtb.OVF_NONE)}
            if overflowed:
                raise RuntimeError(
                    f"text block overflow on rows {sorted(overflowed)}; "
                    "size text blocks for the tick width")

    # -- capacity maintenance --------------------------------------------------

    def observed_head_fraction(self) -> float:
        """Fraction of mixed ticks whose block-table rebalance fired —
        the device-true op-locality estimate (head-concentrated streams
        refill one block and fire near 1.0; spread streams near 0.0).
        The input of :meth:`retune_text_geometry`."""
        ticks = self.rebalance_stats["ticks"]
        if ticks == 0:
            return 0.0
        return self.rebalance_stats["fired"] / ticks

    def retune_text_geometry(self, head_fraction: float | None = None
                             ) -> tuple[int, int]:
        """Re-derive the text block geometry from observed op locality
        and re-block the live table in place (between ticks). The
        re-block is a pure re-layout through the packed flat form —
        occupied-slot document order, text pools and admission marks are
        untouched, so serving continues identically; only the rebalance
        fire RATE changes (resize geometry, not replay frequency —
        ADVICE item 4). Deterministic in (state, head_fraction): a
        restore + replay that re-runs the same retune call re-blocks
        byte-identically. Returns the (possibly unchanged) geometry."""
        if self.merge_state is None:
            raise ValueError("assembly built without text_slots")
        if head_fraction is None:
            head_fraction = self.observed_head_fraction()
        nb, bk = mtb.choose_block_geometry(self.text_slots, self.text_k,
                                           head_fraction)
        if (nb, bk) == self.text_geometry:
            return self.text_geometry
        # Chaos kill class "mid-retune": the layout is about to move
        # wholesale; a crash here loses only volatile device state (the
        # durable records + checkpoint replay rebuild the rows, and the
        # replayed retune re-decides the same geometry).
        faults.crashpoint("pool.mid_retune")
        packed = mtb.to_flat(self.merge_state, slots=nb * bk)
        self.merge_state = mtb.from_flat(packed, nb)
        self.text_geometry = (nb, bk)
        self.rebalance_stats = {"ticks": 0, "fired": 0,
                                "blocks_touched": 0}
        return self.text_geometry

    def compact_text(self) -> None:
        """Zamboni over every text row (mtk.compact at each doc's device
        MSN — the collab-window floor the sequencer maintains), then
        refresh the host's admission high-water marks from the REAL
        device slot counts."""
        if self.merge_state is None:
            raise ValueError("assembly built without text_slots")
        self.merge_state = mtb.rebalance(self.merge_state,
                                         self.seq_state.msn)
        for row, count in _addressable_rows(self.merge_state.count).items():
            if row in self._text_high:
                self._text_high[row] = int(count)
        # Submissions admitted but not yet ticked kept their worst-case
        # charge against the PRE-compact mark; re-charge them or the
        # freed headroom double-counts (silent device overflow).
        for pending in self._pending:
            for row, sub in pending.items():
                if sub.family == "text":
                    self._text_high[row] += 2 * sub.count

    def durable_offset(self, row: int) -> int:
        """Absolute record count of a row's durable log (checkpoint
        cursor)."""
        return (self._durable_base.get(row, 0)
                + len(self.durable.get(row, [])))

    def trim_durable(self, horizons: dict[int, int]) -> None:
        """Retire durable records below the given ABSOLUTE per-row
        offsets — call with the minimum checkpointed offset across hosts
        (the Kafka log-retention analog). Restores against older
        checkpoints become impossible after the trim, exactly as with a
        retention-pruned bus."""
        for row, horizon in horizons.items():
            base = self._durable_base.get(row, 0)
            cut = max(0, min(horizon - base,
                             len(self.durable.get(row, []))))
            if cut:
                del self.durable[row][:cut]
                self._durable_base[row] = base + cut

    # -- failover (checkpointManager.ts:24 analog) -----------------------------

    def _family_states(self) -> dict[str, Any]:
        out: dict[str, Any] = {"seq": self.seq_state, "map": self.map_state}
        if self.merge_state is not None:
            out["text"] = self.merge_state
        if self.matrix_state is not None:
            out["matrix"] = self.matrix_state
        if self.tree_state is not None:
            out["tree"] = self.tree_state
        return out

    def checkpoint_host(self, host_id: int) -> dict:
        """Durable snapshot of one host's rows across EVERY family state
        (+ text pools + per-row durable-log offsets). The checkpoint/
        offset pair is consistent BY CONSTRUCTION when taken between
        ticks (tick() is the only writer, and it records its own tick
        durably before it returns)."""
        port = self.hosts[host_id]
        states = {
            name: jax.tree.map(lambda a: _plane_rows(a, port), state)
            for name, state in self._family_states().items()}
        return {
            "host_id": host_id,
            "start": port.start,
            "stop": port.stop,
            "states": states,
            # Back-compat field-dict views of the two always-on families.
            "seq": dict(states["seq"]._asdict()),
            "map": dict(states["map"]._asdict()),
            "text_pool": {row: self.text_pool[row]
                          for row in range(port.start, port.stop)
                          if row in self.text_pool},
            "log_offsets": {row: self.durable_offset(row)
                            for row in range(port.start, port.stop)},
        }

    def rebalance_from(self, dead_host_id: int, target_host_id: int
                       ) -> None:
        """Reassign a dead host's doc range to a surviving neighbour (the
        Kafka partition-reassignment analog). Ranges must stay contiguous
        for front-door range routing."""
        dead = self.hosts[dead_host_id]
        target = self.hosts[target_host_id]
        if dead.stop != target.start and target.stop != dead.start:
            raise ValueError("rebalance target must be an adjacent range")
        merged = HostPort(target.host_id, min(dead.start, target.start),
                          max(dead.stop, target.stop))
        self.hosts[target_host_id] = merged
        self.hosts[dead_host_id] = HostPort(dead.host_id, dead.start,
                                            dead.start)  # empty range
        # The dead host's buffered frames are LOST (at-least-once:
        # clients resend un-acked frames to the new owner).
        self._pending[dead_host_id] = {}

    def restore_host(self, checkpoint: dict,
                     durable: dict[int, list[dict]],
                     durable_base: dict[int, int]) -> None:
        """Install a dead host's checkpointed rows into THIS assembly and
        replay its durable-log tail through the REAL tick path — map,
        text, matrix and tree records alike (one deltas stream). The
        restored sequencer counters resume seq assignment exactly where
        the log ends — no sequence regression — and clientSeq dedup makes
        an overlapping replay idempotent. Submissions route via the
        CURRENT host ranges, so run :meth:`rebalance_from` (or build the
        replacement assembly with the new ranges) first. Single-controller
        restore: a true multi-process relaunch restores each process's
        own rows with the same codec."""
        lo, hi = checkpoint["start"], checkpoint["stop"]
        idx = np.arange(lo, hi)

        def write(state, rows):
            return jax.tree.map(lambda a, r: a.at[idx].set(r), state, rows)

        states = checkpoint.get("states")
        if states is None:  # legacy two-family checkpoint shape
            states = {"seq": type(self.seq_state)(**checkpoint["seq"]),
                      "map": type(self.map_state)(**checkpoint["map"])}
        self.seq_state = write(self.seq_state, states["seq"])
        self.map_state = write(self.map_state, states["map"])
        if "text" in states:
            self.merge_state = write(self.merge_state, states["text"])
        if "matrix" in states:
            self.matrix_state = write(self.matrix_state, states["matrix"])
            # Rebuild the host-side handle allocators + admission marks
            # from the RESTORED device planes: the next free handle is
            # one past the highest handle any live-or-tombstoned vector
            # run covers (handle_base lives in pool_start, run length in
            # length; axes never recycle handles), and the admission
            # high-water is the real slot count.
            mx = states["matrix"]
            for offset in range(hi - lo):
                row = lo + offset
                if row not in self._mx_handles:
                    continue
                tops = [0]
                for axis in (mx.rows, mx.cols):
                    valid = np.asarray(axis.valid[offset])
                    if valid.any():
                        tops.append(int(
                            (np.asarray(axis.pool_start[offset])
                             + np.asarray(axis.length[offset]))[valid]
                            .max()))
                self._mx_handles[row] = max(tops)
                self._mx_high[row] = [
                    int(np.asarray(mx.rows.count[offset])),
                    int(np.asarray(mx.cols.count[offset]))]
        if "tree" in states:
            self.tree_state = write(self.tree_state, states["tree"])
        for row, pool in checkpoint.get("text_pool", {}).items():
            self.text_pool[row] = pool
        if self.merge_state is not None and checkpoint.get("text_pool"):
            # Admission high-water = the restored rows' REAL device slot
            # counts (exact: the worst-case estimate only ever overshoots
            # the count plane).
            counts = _addressable_rows(self.merge_state.count)
            for row in checkpoint["text_pool"]:
                if row in self._text_high and row in counts:
                    self._text_high[row] = counts[row]

        # Replay the tail one logged tick at a time (records of one row
        # are strictly ordered; distinct rows may interleave freely).
        def tail_of(row: int) -> list[dict]:
            # Offsets in both the checkpoint and the log are ABSOLUTE, so
            # the source log's base is required — defaulting it would
            # silently drop replay ops after a retention trim.
            records = durable.get(row, [])
            start = (checkpoint["log_offsets"].get(row, 0)
                     - durable_base.get(row, 0))
            if start < 0:
                raise ValueError(
                    f"row {row}: durable log trimmed past the checkpoint")
            return records[start:]

        depth = max((len(tail_of(row)) for row in range(lo, hi)),
                    default=0)
        for i in range(depth):
            for row in range(lo, hi):
                tail = tail_of(row)
                if i < len(tail):
                    rec = tail[i]
                    family = rec.get("family", "map")
                    if family == "map":
                        self.submit(row, rec.get("planes", rec["words"]),
                                    rec["cseq0"], rec["ref"],
                                    rec.get("client", 0))
                    else:
                        # Recorded planes carry absolute pool_starts;
                        # _admit re-verifies the pool base and re-extends
                        # the pool with the recorded blob.
                        self.submit_planes(
                            row, family, rec["planes"], rec["count"],
                            rec["cseq0"], rec["ref"], rec["client"],
                            text=rec["text"], pool_base=rec["pool_base"])
            self.tick()

    # -- observability ---------------------------------------------------------

    def global_metrics(self) -> dict[str, int]:
        """psum over the mesh: total sequenced ops + live keys across every
        host's documents (the cross-partition metrics roll-up)."""
        totals = aggregate_metrics(self.mesh, {
            "seq": self.seq_state.seq,
            "present": self.map_state.present.astype(np.int32).sum(axis=1),
        })
        return {name: int(value) for name, value in totals.items()}

    def map_rows(self) -> np.ndarray:
        """Converged map value plane (host copy) for verification.
        Single-process only — a multi-process participant cannot
        materialize the global array; use :meth:`local_map_rows`."""
        return np.asarray(self.map_state.value)

    def local_map_rows(self) -> dict[int, np.ndarray]:
        """{row: value plane} for the rows resident on THIS process's
        devices — the multi-process verification surface."""
        out: dict[int, np.ndarray] = {}
        for shard in self.map_state.value.addressable_shards:
            row_slice = shard.index[0]
            start = row_slice.start if row_slice.start is not None else 0
            data = np.asarray(shard.data)
            for offset in range(data.shape[0]):
                out[start + offset] = data[offset]
        return out

    def text_of(self, row: int) -> str:
        """Materialized visible text of one OWNED text row (host copy of
        the row's segment table + the host pool) — the verification
        surface for text serving."""
        if self.merge_state is None:
            raise ValueError("assembly built without text_slots")
        port = HostPort(-1, row, row + 1)
        state1 = jax.tree.map(lambda a: _plane_rows(a, port),
                              self.merge_state)
        pool = mtk.TextPool(1)
        pool.append(0, self.text_pool[row])
        return mtb.materialize(state1, pool, 0)


class ShardResidency:
    """Per-shard tiered doc residency over one :class:`ShardedServing`
    assembly — the multi-host face of ``server/residency.py``: each host
    range is a fixed pool of device rows, and the REGISTERED document
    population (doc ids) can be arbitrarily larger. A resident doc owns
    one row inside its owning host's range; a cold doc is one host-side
    record (its row's planes across every family + text pool + durable
    log tail) and zero device rows.

    :meth:`resolve` is the front door: it returns the doc's row,
    hydrating on miss — restore the cold record into a recycled row, or
    CLIENT_JOIN the configured lanes through the real sequencer kernel
    for a first-touch doc (never state surgery: a recycled row's blanked
    clientSeq table MUST re-join, or the new doc's cseq dedup would
    inherit the old doc's counters). When the host range is full the LRU
    resident evicts first; a doc with a pending (unticked) submission
    refuses eviction.

    Determinism: recency is dict insertion order, not wall time —
    identical resolve/submit sequences make identical placement
    decisions on every host (the same property the placement tests in
    the single-controller tier rely on).

    Single-process scope: export/blank address device shards, so each
    process manages ONLY rows inside its ``multihost.local_docs`` slice
    (exactly the rows it can checkpoint). Re-tuning text geometry
    invalidates cold text planes — re-hydrate everything first (the
    retune path already requires a settled assembly)."""

    def __init__(self, serving: ShardedServing,
                 join_slots: tuple[int, ...] = (0,),
                 active_hosts: tuple[int, ...] | None = None) -> None:
        self.serving = serving
        self._join_slots = tuple(join_slots)
        # Free rows per host = the intersection of the host's range and
        # this process's addressable slice (reversed so pops hand out
        # low rows first).
        self._free = {
            p.host_id: list(range(
                max(p.start, serving.local_lo),
                min(p.stop, serving.local_hi)))[::-1]
            for p in serving.hosts}
        self.row_of: dict[str, int] = {}
        self._doc_of: dict[int, str] = {}
        # Insertion-ordered dict as the LRU spine: touch re-inserts, so
        # iteration order alone IS the recency order (values unused).
        self._lru: dict[str, None] = {}
        #: doc_id -> cold record (the demoted row's full state).
        self.cold: dict[str, dict] = {}
        # LIVE placement directory (the round-16 tentpole): the hash
        # default is pinned to the GENESIS active-host set — activating
        # a host later must never silently re-route a doc whose state
        # lives elsewhere; new hosts receive docs only through explicit
        # :meth:`migrate` entries in the overlay.
        self.active = (list(active_hosts) if active_hosts is not None
                       else [p.host_id for p in serving.hosts])
        self._genesis = tuple(self.active)
        #: doc -> host overlay (migrated docs); absent = genesis hash.
        self.placement: dict[str, int] = {}
        self.stats = {"hydrations": 0, "cold_hydrations": 0,
                      "evictions": 0, "migrations": 0}
        #: Per-migration blackout seconds (freeze -> serving again on
        #: the target) — the bench's p50/p99 source.
        self.blackouts_s: list[float] = []
        self._blank1: tuple[Any, dict] | None = None  # (geometry, states)

    # -- directory -------------------------------------------------------------

    def host_for(self, doc_id: str) -> int:
        """The doc's CURRENT owning host: the migration overlay when
        present, else the stable genesis hash (the bus-partition
        analog); any process computes the same owner."""
        host = self.placement.get(doc_id)
        if host is not None:
            return host
        import zlib
        return self._genesis[zlib.crc32(doc_id.encode())
                             % len(self._genesis)]

    def activate_host(self, host_id: int) -> None:
        """Bring one host range online as a migration TARGET (the 2->4
        scale-out step): existing docs keep their genesis-hash homes
        until the placement controller migrates them over."""
        if host_id not in range(len(self.serving.hosts)):
            raise KeyError(host_id)
        if host_id not in self.active:
            self.active.append(host_id)

    def hosts_list(self) -> list[int]:
        """Active host ids (the placement-controller backend surface)."""
        return list(self.active)

    def owned(self, host_id: int) -> list[str]:
        """Docs this host currently owns, cold first (cheapest to
        migrate — a cold doc moves by directory flip alone), then
        residents in LRU order (the same order eviction would pick)."""
        return ([d for d in self.cold if self.host_for(d) == host_id]
                + [d for d in self._lru if self.host_for(d) == host_id])

    def load_signals(self, host_id: int) -> dict:
        """One host's load inputs (the PlacementController backend
        surface): owned docs, pending (unticked) submissions as the
        queue depth; the fused tick is one SPMD program so per-host
        tick cost is uniform in this tier (0 = unweighted)."""
        return {"docs": len(self.owned(host_id)),
                "queue_depth": len(self.serving._pending[host_id]),
                "tick_cost_ms": 0.0}

    def migrate(self, doc_id: str, target_host: int) -> int | None:
        """LIVE migration of one doc to another host range: evict to
        the cold record (snapshot + durable-log tail — the PR 12
        carrier), flip the directory, hydrate into the target's row
        pool. Zero acked-durable ops lost: eviction refuses while a
        submission is pending (tick first), and the cold record carries
        every family plane + the durable log across the placement.
        Returns the new device row (None when the doc was cold — a
        directory flip alone moves it). Chaos kill points bracket the
        three phases (tools/chaos.py MIGRATION_KILL_POINTS)."""
        import time as _time
        if target_host not in range(len(self.serving.hosts)):
            raise KeyError(target_host)
        if target_host not in self.active:
            raise ValueError(f"host {target_host} is not active")
        src = self.host_for(doc_id)
        if target_host == src:
            return self.row_of.get(doc_id)
        t0 = _time.perf_counter()
        was_resident = doc_id in self.row_of
        faults.crashpoint("placement.pre_evict")
        if was_resident:
            self.evict(doc_id)  # refuses while a submission is pending
        faults.crashpoint("placement.post_evict")
        self.placement[doc_id] = target_host
        row = None
        if was_resident:
            # Live migration keeps a resident doc resident; a cold doc
            # moves by directory flip alone and hydrates on next touch.
            row = self.resolve(doc_id, host_id=target_host)
        faults.crashpoint("placement.post_hydrate")
        self.stats["migrations"] += 1
        self.blackouts_s.append(_time.perf_counter() - t0)
        return row

    def is_resident(self, doc_id: str) -> bool:
        return doc_id in self.row_of

    def resident_count(self, host_id: int | None = None) -> int:
        if host_id is None:
            return len(self.row_of)
        port = self.serving.hosts[host_id]
        return sum(1 for row in self._doc_of if port.owns(row))

    def _touch(self, doc_id: str) -> None:
        self._lru.pop(doc_id, None)
        self._lru[doc_id] = None

    # -- hydration -------------------------------------------------------------

    def resolve(self, doc_id: str, host_id: int | None = None) -> int:
        """The doc's device row, hydrating it on miss (possibly evicting
        the owning host's LRU resident to free a row)."""
        row = self.row_of.get(doc_id)
        if row is not None:
            self._touch(doc_id)
            return row
        if host_id is None:
            host_id = self.host_for(doc_id)
        port = self.serving.hosts[host_id]
        free = self._free[host_id]
        if not free:
            pending = self.serving._pending[host_id]
            victim = next(
                (d for d in self._lru
                 if port.owns(self.row_of[d])
                 and self.row_of[d] not in pending), None)
            if victim is None:
                raise RuntimeError(
                    f"host {host_id} has no free or evictable row for "
                    f"{doc_id!r} (every resident has a pending "
                    "submission — tick first)")
            self.evict(victim)
        row = free.pop()
        cold = self.cold.pop(doc_id, None)
        if cold is not None:
            self._restore(row, cold)
            self.stats["cold_hydrations"] += 1
        else:
            self._join_fresh(row)
        self.row_of[doc_id] = row
        self._doc_of[row] = doc_id
        self._touch(doc_id)
        self.stats["hydrations"] += 1
        return row

    def _join_fresh(self, row: int) -> None:
        """Activate a first-touch doc's client lanes through the real
        sequencer kernel (one row's JOIN batch; the other rows carry
        zero valid ops)."""
        s = self.serving
        if not self._join_slots:
            return
        b_local = s.local_hi - s.local_lo
        per_row: list[list[dict]] = [[] for _ in range(b_local)]
        per_row[row - s.local_lo] = [
            dict(kind=int(MessageType.CLIENT_JOIN), slot=-1, target=lane,
                 timestamp=1) for lane in self._join_slots]
        ops = seqk.make_op_batch(per_row, b_local, len(self._join_slots))
        ops = multihost.feed(s.mesh, jax.tree.map(np.asarray, ops),
                             global_batch=s.num_docs)
        s.seq_state, out = seqk.process_batch(s.seq_state, ops)
        jax.block_until_ready(out.kind)

    def _restore(self, row: int, rec: dict) -> None:
        s = self.serving

        def write(state, rows):
            return jax.tree.map(lambda a, r: a.at[row].set(r[0]),
                                state, rows)

        for name, planes in rec["states"].items():
            if name == "seq":
                s.seq_state = write(s.seq_state, planes)
            elif name == "map":
                s.map_state = write(s.map_state, planes)
            elif name == "text":
                s.merge_state = write(s.merge_state, planes)
            elif name == "matrix":
                s.matrix_state = write(s.matrix_state, planes)
            elif name == "tree":
                s.tree_state = write(s.tree_state, planes)
            else:
                raise ValueError(f"unknown family {name!r}")
        if "text_pool" in rec and row in s.text_pool:
            s.text_pool[row] = rec["text_pool"]
            s._text_high[row] = rec["text_high"]
        if "mx_high" in rec and row in s._mx_high:
            s._mx_high[row] = list(rec["mx_high"])
            s._mx_handles[row] = rec["mx_handles"]
        if rec["durable"]:
            s.durable[row] = rec["durable"]
        if rec["durable_base"]:
            s._durable_base[row] = rec["durable_base"]

    # -- eviction --------------------------------------------------------------

    def evict(self, doc_id: str) -> None:
        """Demote one resident doc: export its row's planes (every
        family) + host bookkeeping into a cold record, blank the row to
        init fills and recycle it. The row's durable log travels with
        the doc (records are row-relative only through placement, so
        they replay into whatever row the doc hydrates into next)."""
        s = self.serving
        row = self.row_of[doc_id]
        port = s.route(row)
        if row in s._pending[port.host_id]:
            raise ValueError(
                f"{doc_id!r} (row {row}) has a pending submission — "
                "tick before evicting")
        port1 = HostPort(-1, row, row + 1)
        rec: dict[str, Any] = {
            "states": {
                name: jax.tree.map(lambda a: _plane_rows(a, port1), st)
                for name, st in s._family_states().items()},
            "durable": s.durable.pop(row, []),
            "durable_base": s._durable_base.pop(row, 0),
        }
        if row in s.text_pool:
            rec["text_pool"] = s.text_pool[row]
            rec["text_high"] = s._text_high[row]
        if row in s._mx_high:
            rec["mx_high"] = list(s._mx_high[row])
            rec["mx_handles"] = s._mx_handles[row]
        self.cold[doc_id] = rec
        self._blank(row)
        del self.row_of[doc_id]
        del self._doc_of[row]
        self._lru.pop(doc_id, None)
        self._free[port.host_id].append(row)
        self.stats["evictions"] += 1

    def _blank(self, row: int) -> None:
        s = self.serving
        if self._blank1 is None or self._blank1[0] != s.text_geometry:
            overlap = mtk.overlap_words_for(s.num_clients)
            states: dict[str, Any] = {
                "seq": seqk.init_state(1, s.num_clients + 1),
                "map": mk.init_state(1, s.map_slots)}
            if s.merge_state is not None:
                states["text"] = mtb.init_state(
                    1, *s.text_geometry, s.text_props, overlap)
            if s.matrix_state is not None:
                states["matrix"] = mxk.init_state(
                    1, s.matrix_vec_slots, s.matrix_handles,
                    overlap_words=overlap)
            if s.tree_state is not None:
                states["tree"] = tk.init_state(1, s.tree_slots)
            self._blank1 = (s.text_geometry,
                            jax.tree.map(np.asarray, states))
        blanks = self._blank1[1]

        def write(state, rows):
            return jax.tree.map(lambda a, r: a.at[row].set(r[0]),
                                state, rows)

        s.seq_state = write(s.seq_state, blanks["seq"])
        s.map_state = write(s.map_state, blanks["map"])
        if s.merge_state is not None:
            s.merge_state = write(s.merge_state, blanks["text"])
        if s.matrix_state is not None:
            s.matrix_state = write(s.matrix_state, blanks["matrix"])
        if s.tree_state is not None:
            s.tree_state = write(s.tree_state, blanks["tree"])
        if row in s.text_pool:
            s.text_pool[row] = ""
            s._text_high[row] = 0
        if row in s._mx_high:
            s._mx_high[row] = [0, 0]
            s._mx_handles[row] = 0

    def evict_idle(self, keep_per_host: int) -> list[str]:
        """Shrink every host's resident set to ``keep_per_host`` by
        evicting LRU residents (pending-submission docs are skipped —
        they are by definition not idle)."""
        evicted: list[str] = []
        for port in self.serving.hosts:
            excess = self.resident_count(port.host_id) - keep_per_host
            if excess <= 0:
                continue
            for doc in [d for d in self._lru
                        if port.owns(self.row_of[d])]:
                if excess <= 0:
                    break
                row = self.row_of[doc]
                if row in self.serving._pending[port.host_id]:
                    continue
                self.evict(doc)
                evicted.append(doc)
                excess -= 1
        return evicted


class MegaDocLanes:
    """ONE logical document spread over several ROWS of a
    :class:`ShardedServing` assembly — the lane-placement face of the
    mega-doc write tier (rows shard over the mesh, so L lanes are L
    device lanes). Writers hash to lanes (``megadoc.lane_of_writer``);
    the doc-space :class:`~..server.megadoc.DocSequencerMirror` is the
    combiner (dup/gap/refseq/MSN in doc space, doc seqs stamped in
    submission order — the single-row interleaving); each lane's cleaned
    batch sequences on its OWN row through the real device kernel, and
    the converged doc map is the cross-lane LWW fold
    (:func:`~..server.megadoc.fold_map_rows`) through each lane's
    combine log. Lane rows take ref 0 (the doc-space refseq law already
    ran in the mirror). Map-words family only — the text family's
    sequence-parallel serving lives in the merge host's
    ``promote_merge_row`` tier.

    Single-process scope (the verification shape): ``entries()`` reads
    lane rows via the global map planes."""

    def __init__(self, serving: ShardedServing,
                 lane_rows: list[int]) -> None:
        import numpy as np

        from ..server.megadoc import DocSequencerMirror, LaneCombineLog
        if not lane_rows:
            raise ValueError("need at least one lane row")
        self.serving = serving
        self.rows = list(lane_rows)
        self.mirror = DocSequencerMirror()
        self.logs = [LaneCombineLog() for _ in self.rows]
        # Construct AFTER join_all: each lane row's device seq already
        # counts its slot joins, and the combine log must number lane
        # seqs in the DEVICE's space (the map fold's vseq plane carries
        # them) — anchor the log's high water there.
        seqs = np.asarray(serving.seq_state.seq)
        for lane, row in enumerate(self.rows):
            self.logs[lane].seq = int(seqs[row])
        self._slot_of: dict[str, int] = {}
        self._lane_fill = [0] * len(self.rows)

    def join(self, client: str) -> tuple[int, int]:
        """Register a writer: lane by stable hash, client slot within
        the lane's row in join order (the row's joined lanes are the
        capacity — join_all(slots=...) them first). A join revs the
        LOGICAL doc's seq exactly as a sequenced CLIENT_JOIN revs a
        single row's, so the doc seq stream matches a single-row twin
        whose writers joined the same way. Returns (lane, slot)."""
        w = self.mirror.writers.get(client)
        if w is None:
            w = self.mirror.adopt(client, len(self.rows), clu=1)
            self.mirror.seq += 1  # the join's seq rev
        if client in self._slot_of:
            return w.lane, self._slot_of[client]
        slot = self._lane_fill[w.lane]
        if slot >= self.serving.num_clients:
            raise ValueError(
                f"lane {w.lane} writer slots exhausted "
                f"({self.serving.num_clients}); build the assembly with "
                "more num_clients")
        self._lane_fill[w.lane] += 1
        self._slot_of[client] = slot
        return w.lane, slot

    def submit(self, client: str, words, first_cseq: int,
               ref_seq: int = 1):
        """One writer batch through the combiner: the doc-space ticket
        decides (dups trimmed, zero-op outcomes never touch a lane),
        the cleaned batch rides the writer's lane row, and the returned
        :class:`~..server.megadoc.Decision` carries the doc-space ack
        quad."""
        import numpy as np
        w = self.mirror.writers.get(client)
        if w is None:
            self.join(client)
            w = self.mirror.writers[client]
        dec = self.mirror.decide(client, first_cseq, ref_seq,
                                 len(words), ts=1)
        if dec.n_seq == 0:
            return dec
        lane = w.lane
        row = self.rows[lane]
        port = self.serving.route(row)
        if row in self.serving._pending[port.host_id]:
            # Lane collision (one submission per row per tick): run the
            # pending tick first. Doc seqs were already stamped at
            # decide time, so tick boundaries never reorder the doc.
            self.serving.tick()
        self.logs[lane].append(dec.n_seq, dec.first, dec.msn)
        lane_cseq0 = (first_cseq + dec.dups) - w.offset
        self.serving.submit(self.rows[lane],
                            np.asarray(words, np.uint32)[dec.dups:],
                            lane_cseq0, ref_seq=0,
                            client_slot=self._slot_of[client])
        return dec

    def entries(self) -> dict[int, int]:
        """Converged doc map (slot -> value): the cross-lane fold by
        translated doc seq — byte-comparable to a single-row twin
        serving the same batches sequentially."""
        import numpy as np

        from ..server.megadoc import fold_map_rows
        if any(self.serving._pending[p.host_id]
               for p in self.serving.hosts):
            self.serving.tick()  # lane batches still staged: run them
        ms = self.serving.map_state
        present = np.asarray(ms.present)
        value = np.asarray(ms.value)
        vseq = np.asarray(ms.vseq)
        cleared = np.asarray(ms.cleared_seq)
        sources = []
        for lane, row in enumerate(self.rows):
            log = self.logs[lane]
            cs = int(cleared[row])
            sources.append({
                "present": present[row], "value": value[row],
                "vseq": log.to_doc_array(vseq[row].astype(np.int64)),
                "cleared_seq": log.to_doc(cs) if cs >= 1 else cs})
        fold = fold_map_rows(sources)
        return {int(s): int(fold["value"][s])
                for s in np.flatnonzero(fold["present"])}


__all__ = ["ShardedServing", "ShardResidency", "MegaDocLanes",
           "HostPort"]
