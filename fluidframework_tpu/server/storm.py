"""Columnar op-storm fast path — the batched-cadence deployment of the
deli → scriptorium/broadcaster → merger pipeline in ONE fused device tick.

Reference parity: the reference reaches throughput by batching at every
hop — socket.io message arrays, Kafka produce batches
(services-ordering-rdkafka), Mongo batch inserts (scriptorium
lambda.ts:95) — while each document's ticket loop stays per-op JavaScript
(deli/lambda.ts:236). Here the batching goes all the way through the
sequencer: a storm frame carries a whole op batch as packed u32 words
(4 bytes/op, protocol/codec.py storm framing); the host never touches a
per-op Python object between the socket and the device. One flush =

  1. deli      — the CLOSED-FORM storm ticket sequences every doc's
                 batch (full NACK/MSN/dup/gap semantics collapsed to
                 O(1)-per-doc algebra, ops/sequencer.py storm_tickets),
  2. merger    — the Pallas VMEM map fold applies the sequenced ops
                 using the ticket windows WITHOUT a host round-trip
                 (fused jit, ops/map_pallas.py),
  3. scriptorium — one durable columnar record per (doc, tick)
                 (the Mongo batch-insert analog; per-op messages are
                 materialized lazily on the read path, see
                 :func:`materialize_storm_records`),
  4. broadcaster — one compact frame per doc into the fan-out hop,
  5. alfred    — per-frame acks pushed back to the submitting session.

Delivery contract: at-least-once with kernel-side dedup — an un-acked
frame may be resent verbatim; ops whose client_seq the sequencer has
already seen come back OUT_IGNORED (exactly the reference's
clientSequenceNumber dedup, deli/lambda.ts:257).

Storm channels hold LITERAL small-int values (the 20-bit word payload)
addressed by key slot (``k{slot}``); they are the op-storm/load-test
shape (LoadTestDataStore counters), not a general SharedMap replacement —
mixed dict-path traffic on a storm channel is rejected.
"""

from __future__ import annotations

import base64
import contextlib
import functools
import time
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ..ops import map_kernel as mk
from ..ops import map_pallas as mp
from ..ops import matrix_kernel as mxk
from ..ops import mergetree_blocks as mtb
from ..ops import mergetree_kernel as mtk
from ..ops import opcodes as oc
from ..ops import sequencer as seqk
from ..ops import tree_kernel as tk
from ..protocol.codec import TRACE_KEY, trace_context
from ..protocol.messages import MessageType, SequencedDocumentMessage
from ..utils import faults
from .kernel_host import KernelSequencerHost, _next_pow2
from .merge_host import ChannelKey, KernelMergeHost

I32 = jnp.int32


_libc = None


def _malloc_trim() -> None:
    """Release retained glibc arena pages back to the OS (no-op where
    unavailable)."""
    global _libc
    if _libc is None:
        import ctypes

        try:
            _libc = ctypes.CDLL("libc.so.6")
        except OSError:
            _libc = False
    if _libc:
        try:
            _libc.malloc_trim(0)
        except Exception:
            pass


class _TrimGate:
    """Rate limiter for the RSS-hygiene ``malloc_trim`` — the round-5
    serving-loop stall suspect (COVERAGE "Round 6 — known regressions"):
    the call walks every glibc arena and can stall the loop under
    allocation churn. It now runs at most once per :meth:`due` poll
    (callers poll once per flush, OFF the per-tick harvest path) and only
    when BOTH gates open: every ``every`` ticks AND at least ``floor_s``
    of wall clock since the last trim."""

    def __init__(self, every: int = 32, floor_s: float = 5.0,
                 clock: Callable[[], float] = time.monotonic) -> None:
        self.every = max(1, every)
        self.floor_s = floor_s
        self._clock = clock
        self._last_trim = clock()
        self._trimmed_at_tick = 0

    def due(self, ticks: int) -> bool:
        if ticks - self._trimmed_at_tick < self.every:
            return False
        now = self._clock()
        if now - self._last_trim < self.floor_s:
            return False
        self._last_trim = now
        self._trimmed_at_tick = ticks
        return True


#: What ``StormController._stage`` times during WAL replay: nothing.
_NO_STAGE = contextlib.nullcontext()


class _Frame(NamedTuple):
    push: Callable[[dict], None] | None
    rid: Any
    docs: list[tuple[str, str, int, int, int]]  # (doc, client, cseq0, ref, n)
    words: np.ndarray   # u32[sum(counts)] VIEW aliasing the receive buffer
    counts: np.ndarray  # i32[n_docs] per-doc op counts
    meta: np.ndarray    # i32[n_docs, 3] (cseq0, ref, count) columns
    trace: Any = None   # (client tc, session scope) tracer key or None
    staged: tuple = ()  # (decode, admit) ledger stages refunded on shed
    mega: Any = None    # per-entry mega-doc descriptors (megadoc.py)
    tenant: str = "default"  # session-validated tenant (QoS composition)
    t0: int = 0         # ingress monotonic ns (per-tenant ack latency)


def _map_leg(map_state: mk.MapState, words, lo, hi, seq0_for):
    """Windowed map LWW fold: the merger leg of the fused tick. ``lo``/
    ``hi`` bound each row's sequenced op window within ``words``;
    ``seq0_for`` is the row's doc seq before the first windowed op."""
    k = words.shape[1]
    if mp.default_interpret():
        iota = jnp.arange(k, dtype=I32)[None, :]
        words_u = words.astype(jnp.uint32)
        sequenced = (iota >= lo[:, None]) & (iota < hi[:, None])
        map_ops = mk.MapOpBatch(
            valid=sequenced,
            kind=(words_u & 3).astype(I32),
            slot=((words_u >> 2) & 0x3FF).astype(I32),
            value=((words_u >> 12) & 0xFFFFF).astype(I32),
            seq=seq0_for[:, None] + 1 + iota - lo[:, None],
        )
        return jax.vmap(mk._apply_doc)(map_state, map_ops)
    # VMEM LWW fold (ops/map_pallas.py): HBM traffic = planes +
    # 4 bytes/op; the [B, K, S] dense-winner intermediates of the
    # XLA path were the fused tick's dominant cost.
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty:
        return mp.fold_words(map_state, words, lo, hi, seq0_for)
    # A Mosaic kernel is never partitioned automatically: under a serving
    # mesh (ShardedServing ticks inside jax.set_mesh) each device folds
    # its own documents.
    docs = jax.sharding.PartitionSpec(mesh.axis_names)
    return jax.shard_map(mp.fold_words, mesh=mesh, in_specs=docs,
                         out_specs=docs, check_vma=False)(
        map_state, words, lo, hi, seq0_for)


def _ticket_window(counts, k: int, dups, n_seq_doc, seq_before):
    """Per-op (in_window, seq) planes from the closed-form ticket: ops
    [dups, dups+n_seq) of each row's batch sequence as seq_before+1…"""
    lo = dups
    hi = jnp.minimum(dups + n_seq_doc, counts)
    iota = jnp.arange(k, dtype=I32)[None, :]
    in_win = (iota >= lo[:, None]) & (iota < hi[:, None])
    seq = seq_before[:, None] + 1 + iota - lo[:, None]
    return in_win, seq


# Device kernel-stats plane: one tiny i32[KSTATS_WIDTH] vector riding
# the tick's EXISTING readback batch (zero extra device syncs). Indices
# are shared by _storm_tick and _mixed_tick so the harvest/export path
# is layout-agnostic; legs a tick does not run report 0 (the map-only
# _storm_tick never rebalances, so its rebalance cells stay 0 — the
# counters move on the mixed/text serving path and in the merge-host
# pre_tick metrics).
KSTAT_SEQUENCED = 0
KSTAT_DUP_OPS = 1
KSTAT_SENTINEL_DOCS = 2
KSTAT_REBALANCE_FIRED = 3   # ticks whose block-table rebalance fired
KSTAT_BLOCKS_TOUCHED = 4    # blocks the spill/rebuild moved this tick
KSTATS_WIDTH = 5


# Packed-plane field orders for the mixed tick's one-array-per-family
# feed (index 0 is always the submission-valid plane; ``seq`` planes are
# OMITTED — the on-device ticket assigns them).
TEXT_PACK = ("valid", "kind", "pos", "end", "ref_seq", "client",
             "pool_start", "text_len", "prop_key", "prop_val")
MATRIX_PACK = ("valid", "target", "kind", "pos", "end", "count",
               "handle_base", "row", "col", "value", "ref_seq", "client")
TREE_PACK = ("valid", "kind", "node", "parent", "trait", "payload")
#: Columns of the [B, 6] per-doc scalar pack.
SCALAR_PACK = ("slot", "cseq0", "ref", "ts", "seq_counts", "map_counts")


@functools.partial(jax.jit, donate_argnums=(0, 1, 2, 3, 4))
def _mixed_tick(seq_state: seqk.SequencerState,
                map_state, merge_state, matrix_state, tree_state,
                scalars, map_words, text_pack, matrix_pack, tree_pack):
    """ALL-FAMILY fused tick: one closed-form deli ticket sequences every
    document's batch, then EACH channel family applies its rows' windowed
    ops in the same device program — map (LWW fold), merge-tree (segment
    table scan), matrix (two-axis vectors + cells) and tree — exactly the
    reference's one-deltas-stream-for-all-op-types contract
    (deli/lambda.ts:82, scriptorium/lambda.ts:16) with the family routing
    done by per-family valid planes instead of message inspection.

    Family rows share the document axis (row i of every family state IS
    document i); a family whose valid-plane row is empty no-ops on that
    document. Families not configured pass ``None`` and trace away. Per
    family, ALL op planes arrive as ONE packed i32[B, F, K] array (field
    order ``*_PACK``) and the per-doc sequencer inputs as one i32[B, 6]
    (``SCALAR_PACK``) — a tick is five host→device transfers total, not
    one per plane (each transfer pays a dispatch). Ops carry NO seq planes — the ticket assigns seqs on
    device, so sequencing and application never split across a host
    round trip.
    """
    slot, cseq0, ref, ts, seq_counts, map_counts = (
        scalars[:, i] for i in range(6))
    seq_before = seq_state.seq
    seq_state, dups, n_seq_doc, msn_doc = seqk.storm_tickets(
        seq_state, slot, cseq0, ref, ts, seq_counts)

    if map_words is not None:
        lo = dups
        hi = jnp.minimum(dups + n_seq_doc, map_counts)
        map_state = _map_leg(map_state, map_words, lo, hi, seq_before)

    def unpack(pack, names):
        fields = {name: pack[:, i] for i, name in enumerate(names)}
        valid = fields.pop("valid") != 0
        counts = jnp.sum(valid.astype(I32), axis=1)
        win, seqs = _ticket_window(counts, pack.shape[2], dups,
                                   n_seq_doc, seq_before)
        return fields, valid & win, seqs

    text_overflow = None
    rebalance_stats = jnp.zeros((2,), I32)
    if text_pack is not None:
        fields, valid, seqs = unpack(text_pack, TEXT_PACK)
        ops = mtk.MergeOpBatch(valid=valid, seq=seqs, **fields)
        # THE text serving path: the block-structured table
        # (ops/mergetree_blocks.py, O(S/Bk + Bk) per op) replaces the
        # flat O(S)-per-op scan that dominated the mixed tick (VERDICT
        # r5 weak #4), with the block zamboni FUSED into the same
        # program: when any block runs low on worst-case headroom the
        # state spills ONLY the overfull blocks into their neighbors
        # (incremental re-layout; the full pack + uniform redistribution
        # is the fallback, and the tombstone drop at each doc's new MSN
        # is DEFERRED behind the blk_tomb pressure threshold) — the
        # choose_block_geometry contract that makes serving overflow
        # unreachable, at a per-fire cost of log2(Bk) local shifts
        # instead of two log2(S) cascades. rebalance_stats ([fired,
        # blocks_touched]) rides the kstats readback so the decision
        # rate is attributable without extra syncs.
        merge_state, text_overflow = mtb._apply_tick_impl(merge_state,
                                                          ops)
        merge_state, rebalance_stats = mtb._maybe_rebalance_impl(
            merge_state, msn_doc, text_pack.shape[2])
    if matrix_pack is not None:
        fields, valid, seqs = unpack(matrix_pack, MATRIX_PACK)
        ops = mxk.MatrixOpBatch(valid=valid, seq=seqs, **fields)
        matrix_state = mxk._tick(matrix_state, mxk.steps_from_ops(ops))
    tree_overflow = None
    if tree_pack is not None:
        fields, valid, _seqs = unpack(tree_pack, TREE_PACK)
        ops = tk.TreeOpBatch(valid=valid, **fields)
        tree_state, tree_out = tk.apply_tick(tree_state, ops)
        tree_overflow = jnp.sum(tree_out.overflow.astype(I32), axis=1)

    n_seq = n_seq_doc
    first = jnp.where(n_seq > 0, seq_before + 1, oc.INT32_MAX)
    last = jnp.where(n_seq > 0, seq_before + n_seq, 0)
    # The mixed tick's kstats vector (same indices as _storm_tick's):
    # sequenced / dup-dropped totals over rows that submitted a batch,
    # no sentinel leg here, and the text rebalance counters.
    live = seq_counts > 0
    kstats = jnp.concatenate((jnp.stack((
        jnp.sum(jnp.where(live, n_seq_doc, 0)),
        jnp.sum(jnp.where(live, jnp.minimum(dups, seq_counts), 0)),
        I32(0))), rebalance_stats))
    return (seq_state, map_state, merge_state, matrix_state, tree_state,
            n_seq, first, last, msn_doc, tree_overflow, text_overflow,
            kstats)


@functools.partial(jax.jit, donate_argnums=(0, 1))
def _storm_tick(seq_state: seqk.SequencerState, map_state: mk.MapState,
                slot, cseq0, ref, ts, seq_counts,
                map_gather, words, map_counts):
    """deli ticket + merger fold fused into one device program.

    seq inputs are [B_seq] vectors (per-doc constants; 4 bytes/op of
    words is the only [B, K] transfer). The deli leg is the CLOSED-FORM
    storm ticket (:func:`sequencer.storm_tickets`): on the storm shape
    the per-op scan collapses to O(1)-per-doc algebra, so the fused tick
    is merger-bound, not sequencer-bound (VERDICT r3 item 3).
    ``map_gather`` maps each map row to its document's sequencer row so
    the ticket seqs feed the map fold without leaving the device.
    """
    seq_before = seq_state.seq
    seq_state, dups, n_seq_doc, msn_doc = seqk.storm_tickets(
        seq_state, slot, cseq0, ref, ts, seq_counts)

    dups_for = dups[map_gather]
    nseq_for = n_seq_doc[map_gather]
    seq0_for = seq_before[map_gather]
    lo = dups_for
    hi = jnp.minimum(dups_for + nseq_for, map_counts)
    map_state = _map_leg(map_state, words, lo, hi, seq0_for)

    n_seq = nseq_for
    first = jnp.where(n_seq > 0, seq0_for + 1, oc.INT32_MAX)
    last = jnp.where(n_seq > 0, seq0_for + n_seq, 0)
    msn = jnp.where(map_counts > 0, msn_doc[map_gather], 0)
    # Per-doc poison sentinel (summary-drift / invariant violation): a
    # healthy map row never carries a vseq above its doc's post-tick seq,
    # and present slots never hold negative vseq/value. One cheap VPU
    # reduce per row that rides the existing readback batch — the
    # quarantine plane's detection input (harvest freezes flagged docs
    # only; the rest of the batch keeps serving).
    seq_after = seq_state.seq[map_gather]
    drift = jnp.max(jnp.where(map_state.present, map_state.vseq, -1),
                    axis=1) > seq_after
    corrupt = jnp.any(map_state.present
                      & ((map_state.vseq < 0) | (map_state.value < 0)),
                      axis=1)
    bad = drift | corrupt
    # Device-side kernel counter plane: a few VPU reduces packed into ONE
    # tiny i32[KSTATS_WIDTH] output riding the tick's EXISTING readback
    # batch (zero extra device syncs) — total sequenced, duplicate-
    # dropped ops, and sentinel-tripped docs; the rebalance cells stay 0
    # on this map-only leg (the block-table counters live in the mixed
    # tick — shared index layout, see KSTAT_*). Rows with no batch this
    # tick gather row 0's ticket values, so every reduce masks on
    # map_counts > 0.
    live = map_counts > 0
    kstats = jnp.stack((
        jnp.sum(jnp.where(live, n_seq, 0)),
        jnp.sum(jnp.where(live, jnp.minimum(dups_for, map_counts), 0)),
        jnp.sum(jnp.where(live, bad, False).astype(I32)),
        I32(0), I32(0)))
    return seq_state, map_state, n_seq, first, last, msn, bad, kstats


#: Format version stamped on every storm WAL tick header ("v") and on
#: storm snapshot records ("format_version"). Version 0 = the pre-stamp
#: round-7 format (no field); readers accept 0..CURRENT and refuse
#: anything newer (a downgrade must fail loudly, not misparse).
#: v2 (round 15) adds mega-doc lifecycle CONTROL records (docs-less tick
#: headers carrying an "mg" event) and lane-id tick entries — a
#: rolled-back binary must refuse rather than silently drop a promotion.
#: v3 (round 18) adds history-plane CONTROL records (docs-less tick
#: headers carrying an "hp" event: branch forks, trimmed-tick fillers)
#: and a snapshot "history" field — a rolled-back binary must refuse
#: rather than silently drop a branch.
STORM_WAL_VERSION = 3
STORM_SNAPSHOT_VERSION = 3


def choose_pipeline_depth(attribution: dict, current: int = 1) -> int:
    """Pick the serving pipeline depth from OBSERVED stage attribution
    (the round-15 adaptive-depth satellite). BENCH_r14's depth-scaling
    rows are the evidence: at the 10k-doc shape the group fsync is
    commensurate with the dispatch (wal_commit_wait 0.52 vs
    device_dispatch 0.41 of the tick) and overlapping them wins 1.48x;
    at the 2048-doc shape blobs are small, the fsync is cheap, and the
    SERIAL tick wins (pipelining pays a staging generation + lagged acks
    for nothing). So: commit-wait under a quarter of the dispatch time
    -> depth 0 (serial); at least half -> depth >= 1 (overlap); the band
    between is hysteresis (keep the current depth — flapping would
    resize the staging ring every window). Needs >= 8 ticks of ledger
    window to act; returns ``current`` until then."""
    win = attribution.get("_window", {})
    if win.get("ticks", 0) < 8:
        return current
    commit = attribution.get("wal_commit_wait", {}).get("total_ms", 0.0)
    dispatch = attribution.get("device_dispatch", {}).get("total_ms", 0.0)
    if dispatch <= 0.0:
        return current
    ratio = commit / dispatch
    if ratio < 0.25:
        return 0
    if ratio >= 0.5:
        return max(current, 1)
    return current


class StormController:
    """Buffers storm frames and runs the fused tick over the REAL hosts:
    the service's batched deli (KernelSequencerHost) and merge host
    (KernelMergeHost map rows) — the storm path and the per-op path share
    one sequencer state and one map state per document.

    Overload behavior (the graceful-degradation tentpole): the inbound
    frame queue is bounded (``max_pending_docs``) and an optional
    :class:`~fluidframework_tpu.server.riddler.AdmissionController`
    gates the tick ingress; refused frames get a busy-nack carrying
    ``retry_after_s`` instead of queueing without bound. A per-doc
    poison (device sentinel tripping on a tick output) quarantines ONLY
    that document — its in-flight ops nack retryable, catch-up reads
    keep serving from the (poison-free) durable records with
    :meth:`quarantined_map_entries` as the server-side scalar fold, and
    :meth:`readmit_doc` rebuilds it from snapshot + WAL replay while
    every other doc keeps full-rate serving. A WAL whose fsync breaker
    opens degrades the controller to read-only broadcast mode until the
    half-open probes heal it."""

    #: Per-op count sanity bound (one doc's batch within one frame).
    MAX_COUNT = 1 << 16

    def __init__(self, service, seq_host: KernelSequencerHost,
                 merge_host: KernelMergeHost, datastore: str = "default",
                 channel: str = "root",
                 flush_threshold_docs: int = 4096,
                 max_key_slots: int = 64,
                 pipeline_depth: int | str = 1,
                 spill_dir: str | None = None,
                 durability: str | None = None,
                 snapshots=None,
                 snapshot_interval_ticks: int | None = None,
                 admission=None,
                 max_pending_docs: int | None = None,
                 busy_retry_s: float = 0.05,
                 doc_index_retention_ticks: int | None = None,
                 wal_commit_latency_s: float = 0.0,
                 tenant_weights: dict[str, float] | None = None,
                 tenant_weight_source=None,
                 tick_slot_budget: int | None = None,
                 qos_borrow_fraction: float = 0.5,
                 logger=None) -> None:
        self.service = service
        self.seq_host = seq_host
        self.merge_host = merge_host
        self.datastore = datastore
        self.channel = channel
        self.flush_threshold_docs = flush_threshold_docs
        # Storm words address key slots directly; the map state must be
        # wide enough BEFORE any tick (out-of-range slots would silently
        # no-op on device while the durable history replays them).
        self.max_key_slots = min(1024, max_key_slots)  # 10-bit slot field
        if merge_host._map_slots < self.max_key_slots:
            merge_host._grow_map_slots(self.max_key_slots)
        self._frames: list[_Frame] = []
        self._pending_docs = 0
        # Bounded cohort LRU: (membership_gen, ((doc, client), ...)) ->
        # resolved (seq_rows, slots, map_rows) arrays. Residency churn
        # guarantees ALTERNATING cohorts (hydrations rotate the doc set),
        # so the old single-entry cache thrashed every tick — a small LRU
        # keeps each live cohort's resolution warm, and the hit/miss
        # counters (storm.cohort_cache.*) make a thrash observable.
        from ..utils import CountedLRU
        self._cohort_cache = CountedLRU(
            8, registry=merge_host.metrics, prefix="storm.cohort_cache")
        self._tick_counter = 0  # tick blob index
        # Tick words blobs: the bulk of the scriptorium payload. With a
        # spill dir they ride the disk WAL (the Mongo-storage analog —
        # serving-host RSS stays bounded however long the run, VERDICT
        # r4 weak #6); without one they stay in process memory like the
        # rest of the in-memory StateStore.
        self._tick_blobs: dict[int, bytes] = {}
        # doc -> [(first_seq, last_seq, tick_id)] for ticks that
        # sequenced ops — the compact in-RAM index over the tick blobs.
        self._doc_ticks: dict[str, list[tuple[int, int, int]]] = {}
        # Durability mode of the tick WAL (CRC-framed OpLog either way):
        #   "group" — async group-commit writer (durable_store.
        #             GroupCommitLog): the harvest path pays a queue put;
        #             fsyncs batch on the writer thread; ACKS ARE WITHHELD
        #             until the durability watermark passes the tick, so
        #             an acked op can never be lost to a crash.
        #   "sync"  — append + fdatasync inline per tick (the maximally
        #             conservative shape; the bench durability column).
        #   "none"  — append only, no fsync (the round-5 behavior: a
        #             process kill keeps the data, a host crash may not).
        # None (default) = "group" when a spill dir is given, else no WAL.
        # An EXPLICIT "group"/"sync" without a spill dir is a
        # misconfiguration and must fail loudly — silently serving
        # without the acked-durable contract the caller asked for would
        # void the one guarantee this layer exists to give.
        if durability not in ("group", "sync", "none", None):
            raise ValueError(f"unknown durability mode {durability!r}")
        if durability in ("group", "sync") and spill_dir is None:
            raise ValueError(
                f"durability={durability!r} needs a spill_dir (the WAL "
                "lives there); pass durability='none' for WAL-less "
                "serving")
        if durability is None:
            durability = "group" if spill_dir is not None else "none"
        self.durability = durability
        self._blob_log = None
        self._group_wal = None
        self._spill_path = None
        # (tick_id, [(frame, ack payload)], harvest_ns, ledger record)
        # awaiting the durability watermark — drained in tick order on
        # the serving thread.
        self._unacked: list[tuple[int, list, int, dict | None]] = []
        if spill_dir is not None:
            import pathlib

            from ..native import OpLog
            from .durable_store import GroupCommitLog
            root = pathlib.Path(spill_dir)
            root.mkdir(parents=True, exist_ok=True)
            path = root / "storm_tick_words.log"
            self._spill_path = path  # trim_tick_blobs rewrite target
            if durability == "group":
                # commit_latency_s models a replicated durable log's
                # quorum round trip (bench regime); 0 = local disk.
                self._group_wal = GroupCommitLog(
                    path, commit_latency_s=wal_commit_latency_s)
                self._blob_log = self._group_wal
            else:
                self._blob_log = OpLog(path)
            # Restart/reuse recovery: the RAM (first, last, tick) index
            # and the tick counter rebuild from the journaled blobs, so
            # catch-up reads survive a serving-host restart and a reused
            # spill dir cannot alias fresh tick ids onto stale blobs.
            for tick_id in range(len(self._blob_log)):
                header, _off = self._parse_header(
                    bytes(self._blob_log.read(tick_id)))
                for entry in header["docs"]:
                    doc, _c, _c0, _r, _n, ns, fs, ls, _m, _w = entry
                    if ns > 0:
                        self._doc_ticks.setdefault(doc, []).append(
                            (fs, ls, tick_id))
            self._tick_counter = len(self._blob_log)
        # Device-pool snapshot backend (GitSnapshotStore surface). With an
        # interval, flush() checkpoints every N ticks; recover() restores
        # the head + replays the WAL tail (see checkpoint()/recover()).
        self.snapshots = snapshots
        self.snapshot_interval_ticks = snapshot_interval_ticks
        self._last_checkpoint_tick = self._tick_counter
        self._in_checkpoint = False
        # WAL-replay mode (recover()): reuse THE serving tick verbatim but
        # pin timestamps to the recorded ones and skip re-persisting.
        self._replay = False
        self._replay_ts: int | None = None
        self._trim_gate = _TrimGate()
        # Tick-ingress admission (the alfred/deli throttle seam moved to
        # where batching amplifies it): a bounded inbound queue + token
        # buckets; refusals are busy-nacks, never silent drops or OOM.
        self.admission = admission
        self.max_pending_docs = max_pending_docs
        self.busy_retry_s = busy_retry_s
        if admission is not None and max_pending_docs is not None:
            admission.add_pressure_probe(
                lambda: self._pending_docs / max(1, self.max_pending_docs))
        # Multi-tenant QoS plane (server/qos.py, the round-17 tentpole):
        # deficit-weighted fair tick composition over per-tenant pending
        # queues. ``tick_slot_budget`` bounds one tick's doc slots (None
        # = unbounded — composition then only orders, exactly the
        # legacy cohort for single-tenant serving); ``tenant_weights``
        # configures relative shares (unlisted tenants weigh 1.0).
        # Scheduler state (deficits + rotation) rides every
        # multi-tenant tick's WAL header and the snapshot, so recovery
        # resumes composing exactly where the crash stopped.
        # ``tenant_weight_source`` derives weights from tenant RECORDS
        # (riddler paid tiers) for tenants with no explicit config; the
        # resolved weight journals with the scheduler state.
        from .qos import TenantScheduler
        self.qos = TenantScheduler(weights=tenant_weights,
                                   weight_source=tenant_weight_source,
                                   registry=merge_host.metrics)
        self.tick_slot_budget = tick_slot_budget
        # Weighted-shed borrow threshold: a tenant past its weighted
        # pending share still buffers while the GLOBAL queue is below
        # this fraction of max_pending_docs (work conservation); past
        # it, the over-share tenant sheds first.
        self.qos_borrow_fraction = qos_borrow_fraction
        # Quarantine plane: doc -> {"reason", "tick"}. A quarantined doc
        # is frozen out of cohorts (submits nack retryable) and serves
        # reads through the scalar record fold until readmit_doc().
        self.quarantined: dict[str, dict] = {}
        # Tiered hot/cold residency (server/residency.py attaches
        # itself): when set, _admit hydrates cold docs (or busy-nacks a
        # stampede), WAL replay hydrates on first touch, and eviction
        # trims the per-doc bookkeeping below.
        self.residency = None
        # Mega-doc write scale-out (server/megadoc.py attaches itself):
        # promoted docs serve up to L writer frames per tick through
        # per-lane sub-sequencer rows + the host combiner.
        self.megadoc = None
        # History plane (server/history.py attaches itself): time-travel
        # reads off the cold path, named branches journaled as "hp" WAL
        # controls, and the background summarization compactor driven
        # from the flush maintenance cadence below.
        self.history = None
        # Cluster placement (parallel/placement.py attaches a per-host
        # router): when set, frames naming docs another host owns shed
        # with a "moved" nack carrying the owner as ``moved_to`` (the
        # client redials through the reconnect/backoff path), and docs
        # mid-migration shed "migrating" with a retry hint — never
        # sequenced on the wrong host, never silently dropped.
        self.placement = None
        # Replication plane (server/replication.py attaches itself):
        # when set, client acks gate on min(durable, REPLICATED)
        # watermarks — an acked op survived a follower quorum, not just
        # this host's disk — and a fenced (demoted) plane sheds every
        # frame with a "moved" nack naming the promoted incarnation.
        self.replication = None
        self._in_round = False  # mid-_flush_round (evictions refuse)
        # Opt-in retention for the per-doc (first, last, tick) index:
        # entries whose tick falls below ``tick_counter - retention``
        # drop at harvest. Mirrors parallel/serving.py's
        # durable_retention_ticks contract — catch-up reads older than
        # the horizon become impossible (clients that far behind reload
        # from a snapshot), and in exchange a long-lived host's index
        # RAM is bounded by the retention window, not total history.
        # None (default) keeps the full index.
        self.doc_index_retention_ticks = doc_index_retention_ticks
        #: Ticks each doc participated in (telemetry: the zero-lost-ticks
        #: invariant for a quarantined doc's batch peers asserts on this).
        self.doc_tick_counts: dict[str, int] = {}
        # wal_fsync_ns / wal_batches mirror the group-commit writer's
        # counters (refreshed at harvest and flush): fsync time apart
        # from the serving thread's late drains.
        self.stats = {"ticks": 0, "sequenced_ops": 0, "submitted_ops": 0,
                      "nacked_or_ignored_ops": 0,
                      "shed_frames": 0, "shed_ops": 0,
                      "quarantined_docs": 0, "readmitted_docs": 0,
                      "degraded_rejects": 0, "quorum_rejects": 0,
                      "wal_fsync_ns": 0, "wal_batches": 0}
        # Observability plane (the round-10 tentpole): one fixed-shape
        # stage record per tick into a ring buffer + per-stage Histograms
        # in the shared registry (alfred get_metrics exports them; the
        # monitor renders the attribution bar), and a per-op trace joiner
        # for frames that carry a sampled trace id ("tc" header field).
        # Stages are timed through ledger.stage(), which also puts each
        # on the profiler's clock as an ff.storm.stage.* span; a record
        # is cut at each harvest and holds everything timed since the
        # previous one (the bridge pump times its waits and idle passes
        # into the same ledger).
        from ..utils import NullLogger, StageLedger, TraceSpans
        self.logger = logger if logger is not None else NullLogger()
        self.ledger = StageLedger(registry=merge_host.metrics,
                                  prefix="storm.stage")
        self.tracer = TraceSpans(logger=self.logger)
        self._trace_seq = 0  # per-submission tracer-key disambiguator
        # Server-side sampling cap: the CLIENT picks which frames carry
        # a trace id, but one connection stamping every frame must not
        # commandeer the serving thread's tracer (hop observes, span
        # ring) — past this many traced frames per tick round, extra
        # trace ids are ignored (the frame still serves normally).
        self.max_traces_per_tick = 64
        self._traced_pending = 0
        # Depth-N pipeline (SURVEY §7 hard part (c), round-14 tentpole):
        # up to N ticks stay in flight; each round HARVESTS the due tick
        # BEFORE staging the next one, so tick N's WAL append (and its
        # group fsync, on the writer thread) starts as soon as N's
        # readback lands and then runs CONCURRENT with tick N+1's
        # scatter + device dispatch — the two dominant stages of the
        # durable tick (BENCH_r10: wal_commit_wait 0.52 + device_dispatch
        # 0.41, formerly back-to-back). Acks stay withheld on the durable
        # watermark exactly as before — they lag dispatch by ≤ depth
        # ticks, which is why flow-controlled senders size their window
        # ≥ depth + 1. Depth 1 is the default; depth 0 is the serial
        # fallback (dispatch → readback → append → fsync barrier → ack,
        # per round — the pre-pipelining shape, kept as the A/B twin and
        # for request-response senders that gate on every ack).
        # pipeline_depth="auto" (the round-15 adaptive-depth satellite):
        # start overlapped and re-decide from the ledger's OBSERVED
        # wal_commit_wait vs device_dispatch shares every adaptation
        # window (choose_pipeline_depth) — the BENCH_r14 depth-scaling
        # rows showed the serial tick wins exactly where the fsync is
        # cheap, which no static constant can know up front.
        self._auto_depth = pipeline_depth == "auto"
        self._depth_adapted_at = 0
        self.depth_adapt_every = 64  # ticks between adaptation checks
        self.pipeline_depth = 1 if self._auto_depth \
            else max(0, pipeline_depth)
        self._inflight: list[dict] = []
        # Host staging generations (double/N-buffered): the scatter
        # arrays a dispatched tick's device transfer may still alias are
        # never the ones the next round writes — see _staging_gen.
        self._staging: list[dict | None] = [None] * (self.pipeline_depth
                                                     + 1)
        self._staging_idx = 0
        merge_host.metrics.gauge("storm.pipeline.depth").set(
            self.pipeline_depth)
        service.storm = self

    # -- front-door entry ------------------------------------------------------

    def submit_frame(self, push: Callable[[dict], None] | None,
                     header: dict, payload: memoryview,
                     tenant_id: str = "default",
                     client_id: str | None = None,
                     ingress_ns: int | None = None) -> None:
        """One decoded storm frame from a session; ack is pushed after the
        tick that sequences it. Malformed frames raise ValueError BEFORE
        anything is buffered — a bad frame must fail alone, never poison
        co-buffered frames from other sessions.

        ``tenant_id``/``client_id`` are the admission identities and must
        come from the SESSION (token-validated tenant, service-assigned
        client id) — never from the frame header, which the client
        controls (a self-stamped tenant would mint itself a fresh bucket
        per frame).

        ``ingress_ns`` is the transport's receive timestamp
        (``time.monotonic_ns``), stamped BEFORE the codec decode so the
        ledger's ingress_decode stage covers it (None = entry here). A
        front door that already opened the ``ingress_decode`` stage at
        receive (the bridge pump) has it closed here, once the frame is
        validated. A frame whose header carries a sampled trace id
        (``"tc"``) gets its ingress/admit hops marked on the controller's
        tracer."""
        ingress = None
        if not self._replay:
            ingress = self.ledger.open_stage()
            if ingress is None or ingress.name != "ingress_decode":
                ingress = self.ledger.stage("ingress_decode",
                                            since_ns=ingress_ns).__enter__()
            ingress_ns = ingress.t0
        elif ingress_ns is None:
            ingress_ns = time.monotonic_ns()
        parsed = None
        try:
            parsed = self._parse_frame(header, payload)
        finally:
            if ingress is not None:
                ingress.__exit__(None, None, None)
                if parsed is None:
                    # Never buffered: no tick inherits its decode time.
                    self.ledger.refund(ingress)
        docs, words, counts, meta = parsed
        offset = len(words)
        # Admission gates run AFTER validation (a malformed frame is the
        # sender's error, not overload) and only on live traffic — replay
        # (recovery / readmit) re-runs already-admitted history.
        # The tracer key pairs the client's id with a PER-SUBMISSION
        # counter: clients choose their trace ids independently (two
        # connections sampling the same small integer must never
        # interleave marks on one span), and a shed frame's orphaned
        # marks can never be joined by a later frame reusing the id.
        # The ack carries back the client's raw id (_stamp_trace_ack
        # unpacks the tuple).
        tc = None if self._replay else trace_context(header)
        if not isinstance(tc, (int, str)):
            tc = None  # the field is client-opaque JSON; the tracer
            # keys a dict on it, so unhashable shapes are ignored — a
            # valid frame must never be nacked over its trace id.
        trace = None
        staged = ()
        if not self._replay:
            with self.ledger.stage("admission") as admission:
                retry = self._admit(push, header, docs, offset,
                                    tenant_id, client_id)
            staged = (ingress, admission)
            if retry is not None:
                # Shed: its decode/admit ns never reaches a tick. Charged
                # only once the frame is BUFFERED — shed frames' time
                # must not inflate a surviving tick's attribution (a
                # frame shed LATER, at quarantine, refunds via staged).
                for stage in staged:
                    self.ledger.refund(stage)
                return
            # The trace slot likewise allocates only now: a traced-but-
            # shed frame must not consume the per-tick cap (tracing
            # would starve during exactly the overload it should
            # diagnose).
            if tc is not None \
                    and self._traced_pending < self.max_traces_per_tick:
                trace = (tc, self._trace_seq)
                self._trace_seq += 1
                self._traced_pending += 1
                self.tracer.mark(trace, "ingress", ingress_ns)
                self.tracer.mark(trace, "admit", admission.t1)
        # Mega-doc ingress: promoted-doc entries are rewritten to their
        # writers' LANE sub-doc ids (stateless hash — up to L writer
        # frames of one doc become DISJOINT cohort members and serve in
        # ONE tick). Doc-level sequencing decisions wait for cohort
        # selection (decide_frame) so doc-seq order == WAL order ==
        # replay order. Admission above ran on the PARENT ids.
        mega = None
        if self.megadoc is not None and not self._replay:
            self.megadoc.observe_writers(docs)
            mega = self.megadoc.ingress_frame(docs)
        self._frames.append(_Frame(push, header.get("rid"), docs, words,
                                   counts, meta, trace, staged, mega,
                                   tenant_id, ingress_ns))
        self._pending_docs += len(docs)
        self.stats["submitted_ops"] += offset
        if not self._replay:
            self.qos.note_submitted(tenant_id, offset)
            self.qos.note_buffered(tenant_id, len(docs))
            if tenant_id != "default":
                # Placement input (multi-tenant only — the single-tenant
                # hot path stays untouched): which tenant owns each doc.
                self.qos.note_doc_tenants(tenant_id,
                                          (d for d, *_ in docs))
        if self._pending_docs >= self.flush_threshold_docs:
            # Threshold-triggered: only run FULL rounds; a partial tail
            # (next tick's early frames) waits for its cohort instead of
            # fragmenting into tiny device ticks.
            self.flush(force=False)

    def _parse_frame(self, header: dict, payload: memoryview) -> tuple:
        """Validate one storm frame: (docs, words, counts, meta), or
        ValueError before anything is buffered."""
        entries = header.get("docs")
        if not isinstance(entries, list) or not entries:
            raise ValueError("storm frame without docs")
        docs: list[tuple[str, str, int, int, int]] = []
        seen: set[str] = set()
        for entry in entries:
            if not (isinstance(entry, (list, tuple)) and len(entry) == 5):
                raise ValueError(f"bad storm doc entry: {entry!r}")
            # NB the entry's writer id is not the SESSION identity that
            # admission keys on (submit_frame's ``client_id``): the frame
            # is client-controlled.
            doc_id, doc_client, cseq0, ref_seq, count = entry
            count = int(count)
            if not 0 < count <= self.MAX_COUNT:
                raise ValueError(f"bad storm count {count} for {doc_id!r}")
            if doc_id in seen:
                # One sequencer row per doc per tick: the numpy scatter is
                # last-writer-wins, so an in-frame repeat would silently
                # drop the first batch while acking it as sequenced.
                raise ValueError(f"doc {doc_id!r} repeats within one frame")
            seen.add(doc_id)
            docs.append((str(doc_id), str(doc_client), int(cseq0),
                         int(ref_seq), count))
        # Columnar from here down: ONE payload view + per-doc count/meta
        # arrays — no per-doc np.frombuffer, no byte copy (the words view
        # aliases the receive buffer all the way into the tick scatter).
        meta = np.array([entry[2:] for entry in docs], np.int32)
        counts = meta[:, 2]
        offset = int(counts.sum())
        if offset * 4 > len(payload):
            raise ValueError("storm payload shorter than doc counts")
        words = np.frombuffer(payload, np.uint32, offset)
        max_slot = int((words & np.uint32(0xFFC)).max()) >> 2 \
            if offset else 0
        if max_slot >= self.max_key_slots:
            raise ValueError(
                f"storm key slot {max_slot} >= max_key_slots "
                f"{self.max_key_slots}")
        return docs, words, counts, meta

    def _admit(self, push, header: dict, docs: list, n_ops: int,
               tenant_id: str, client_id: str | None) -> float | None:
        """Shed checks for one validated frame, in deterministic order:
        quarantine, degraded (WAL breaker open), bounded queue, token
        buckets. A refusal pushes ONE busy-nack with ``retry_after_s``
        and returns the hint; None admits."""
        if self.replication is not None and self.replication.fenced:
            # Demoted ex-leader (a follower promoted over this
            # incarnation): EVERY frame sheds with the new leader as
            # ``moved_to`` — sequencing here would fork the history the
            # promoted incarnation is already extending. Same nack
            # shape as a placement move, so the PR 16 client redial
            # machinery handles both.
            target = self.replication.moved_to
            return self._shed(
                push, header, n_ops, "moved", self.busy_retry_s,
                docs=[d for d, *_ in docs],
                moved_to={d: target for d, *_ in docs})
        if self.placement is not None:
            # Ownership first — the cheapest check, and a frame for a
            # foreign doc must never consume this host's quarantine /
            # queue / token state. Whole-frame refusal (acks are
            # positional per frame); ``moved_to`` names each moved
            # doc's owning host so the client redials it directly.
            moved: dict[str, str] = {}
            frozen = False
            for d, *_ in docs:
                code, owner = self.placement.route(d)
                if code == "moved":
                    moved[d] = owner
                elif code == "migrating":
                    frozen = True
            if frozen:
                # Mid-migration blackout: the doc is between hosts
                # (evict-to-cold → hydrate); the retry hint is the
                # expected blackout window, after which the route
                # resolves to "moved" (or back to this host).
                return self._shed(push, header, n_ops, "migrating",
                                  self.placement.retry_after_s,
                                  docs=[d for d, *_ in docs])
            if moved:
                return self._shed(push, header, n_ops, "moved",
                                  self.placement.retry_after_s,
                                  docs=[d for d, *_ in docs],
                                  moved_to=moved)
        qdocs = [d for d, *_ in docs if d in self.quarantined]
        if qdocs:
            # The WHOLE frame is refused (acks are positional per frame,
            # so it cannot be split): "docs" lists everything dropped,
            # "quarantined" the offending subset — the client resubmits
            # the healthy docs in their own frame immediately and the
            # quarantined ones after readmission.
            return self._shed(push, header, n_ops, "quarantined",
                              self.busy_retry_s,
                              docs=[d for d, *_ in docs],
                              quarantined=qdocs)
        if self.wal_degraded:
            self.stats["degraded_rejects"] += 1
            if self._group_wal.failed:
                # TERMINAL writer death (index skew, bad payload — not a
                # disk that may heal): retrying is pointless; say so
                # instead of promising a cooldown that never ends.
                return self._shed(push, header, n_ops, "wal-failed",
                                  self.busy_retry_s, retryable=False)
            cooldown = self._group_wal.breaker.cooldown_s
            return self._shed(push, header, n_ops, "degraded",
                              max(cooldown, self.busy_retry_s))
        if (self.replication is not None
                and not self.replication.quorum_ok):
            # Follower quorum lost (lease-based failure detector,
            # server/transport.py): writes PARK — admitted and buffered
            # FIFO, never acked, because _flush_round declines rounds —
            # while the outage is young. Past ``park_max_s`` new frames
            # shed with a retry hint instead of growing the parked
            # queue without bound. Either way: never ack-without-quorum.
            deg = self.replication.quorum_degraded_s()
            if deg is not None and deg >= self.replication.park_max_s:
                self.stats["quorum_rejects"] += 1
                return self._shed(
                    push, header, n_ops, "quorum-lost",
                    max(self.busy_retry_s,
                        self.replication.park_max_s / 2))
        if self.max_pending_docs is not None:
            n = len(docs)
            cap = self.qos.pending_cap(tenant_id, self.max_pending_docs)
            # Shed when the GLOBAL bound is hit (nobody may buffer past
            # it), or — weighted shed — when THIS tenant is past its
            # weighted pending share while the global queue is past the
            # borrow threshold: the over-share tenant sheds first, and
            # borrowing beyond the share is free only while the queue
            # is shallow. Either way the busy-nack's retry hint is
            # per-tenant, scaled by the tenant's OWN backlog relative
            # to its share — the abuser backs off hardest.
            over_global = self._pending_docs + n > self.max_pending_docs
            over_share = (
                cap is not None
                and self.qos.pending_docs.get(tenant_id, 0) + n > cap
                and self._pending_docs + n > self.max_pending_docs
                * self.qos_borrow_fraction)
            if over_global or over_share:
                self.qos.note_shed(tenant_id, n_ops)
                return self._shed(
                    push, header, n_ops, "busy",
                    self.qos.shed_hint(tenant_id, self.busy_retry_s,
                                       self.max_pending_docs),
                    tenant=tenant_id)
        if self.admission is not None:
            retry = self.admission.admit_write(tenant_id, client_id,
                                               weight=n_ops)
            if retry is not None:
                return self._shed(push, header, n_ops, "throttled", retry)
        if self.residency is not None:
            cap = self.residency.max_resident
            if cap is not None and len(docs) > cap:
                # TERMINAL: a single frame naming more distinct docs
                # than the pool holds can never be admitted — no amount
                # of eviction makes room while the frame itself excludes
                # every named doc. Say so instead of promising a retry
                # that cannot succeed (the wal-failed precedent).
                return self._shed(push, header, n_ops, "frame-too-wide",
                                  self.busy_retry_s,
                                  docs=[d for d, *_ in docs],
                                  retryable=False)
            # Tiered residency LAST — hydration is the one expensive
            # gate (snapshot read + row restore, and a full pool pays an
            # eviction's durability barrier), so frames the O(1)
            # queue/throttle checks would shed anyway must never reach
            # it. A hydration stampede or a full pool busy-nacks the
            # WHOLE frame with the bucket's laddered retry hint —
            # cold-doc storms degrade to queued hydrations, never to
            # pool growth or OOM.
            retry, code = self.residency.admit_docs(
                [d for d, *_ in docs])
            if retry is not None:
                return self._shed(push, header, n_ops, code, retry,
                                  docs=[d for d, *_ in docs])
        return None

    def _shed(self, push, header: dict, n_ops: int, code: str,
              retry_after_s: float, docs: list | None = None,
              quarantined: list | None = None,
              retryable: bool = True,
              moved_to: dict | None = None,
              tenant: str | None = None) -> float:
        self.stats["shed_frames"] += 1
        self.stats["shed_ops"] += n_ops
        self.merge_host.metrics.counter("storm.shed_ops").inc(n_ops)
        if tenant is not None:
            self.merge_host.metrics.counter(
                f"storm.tenant.{tenant}.shed_frames").inc()
        if push is not None:
            nack = {"rid": header.get("rid"), "storm": True,
                    "error": code, "retryable": retryable,
                    "retry_after_s": retry_after_s}
            if docs:
                nack["docs"] = docs  # EVERY doc whose ops were dropped
            if quarantined:
                nack["quarantined"] = quarantined
            if moved_to:
                nack["moved_to"] = moved_to  # doc -> owning host label
            push(nack)
        return retry_after_s

    # -- the tick --------------------------------------------------------------

    def flush(self, force: bool = True) -> None:
        while self._frames and (
                force or self._pending_docs >= self.flush_threshold_docs):
            if not self._flush_round(require_full=not force):
                break
        if force:
            self._harvest()
            if self._group_wal is not None and self._unacked:
                from .durable_store import WalDegradedError
                try:
                    # Drain barrier: a forced flush settles everything, so
                    # withheld acks go out now — after their fsync, never
                    # before (the acked-durable contract).
                    self._group_wal.sync()
                except WalDegradedError:
                    # Fsync breaker open: acks STAY withheld (they are
                    # not durable) and the controller serves read-only —
                    # new writes nack at _admit until the half-open
                    # probes heal the WAL and a later flush drains here.
                    self.merge_host.metrics.counter(
                        "storm.degraded_flushes").inc()
                else:
                    self._drain_durable_acks()
            self._sync_wal_stats()
        if (self.snapshot_interval_ticks is not None
                and self.snapshots is not None
                and not self._replay and not self._in_checkpoint
                and not self.wal_degraded and not self.quarantined
                and self._tick_counter - self._last_checkpoint_tick
                >= self.snapshot_interval_ticks):
            self.checkpoint()
        # Maintenance cadence OFF the per-tick path: mega-doc auto
        # promotion/demotion and the adaptive pipeline depth re-decide
        # here (never inside a round), then the RSS arena trim.
        if self.megadoc is not None and not self._replay:
            self.megadoc.maybe_adapt()
        if self.history is not None and not self._replay \
                and not self._in_checkpoint:
            # Summarization compaction cadence (server/history.py): roll
            # long WAL tails into fresh summaries + trim per retention.
            self.history.maybe_compact()
        if self._auto_depth and not self._replay and (
                self.stats["ticks"] - self._depth_adapted_at
                >= self.depth_adapt_every):
            self._depth_adapted_at = self.stats["ticks"]
            self.set_pipeline_depth(choose_pipeline_depth(
                self.ledger.attribution(), self.pipeline_depth))
        # RSS hygiene OFF the per-tick path: at most one arena trim per
        # flush, gated on tick count AND a wall-clock floor (the round-5
        # serving-loop stall suspect — see _TrimGate).
        if self._trim_gate.due(self.stats["ticks"]):
            _malloc_trim()

    def set_pipeline_depth(self, depth: int) -> None:
        """Change the serving pipeline depth between rounds: settle the
        in-flight ticks first (a shrink must not orphan them), then the
        staging-generation ring resizes lazily on the next round."""
        depth = max(0, int(depth))
        if depth == self.pipeline_depth:
            return
        self._harvest()
        self.pipeline_depth = depth
        self.merge_host.metrics.gauge("storm.pipeline.depth").set(depth)

    @property
    def wal_degraded(self) -> bool:
        """True while the WAL writer's fsync circuit breaker is open:
        the controller serves reads and withholds acks, and _admit nacks
        every write with a retryable "degraded" code. Clears itself when
        a half-open probe fsyncs successfully."""
        return (self._group_wal is not None
                and self._group_wal.breaker.is_open)

    @property
    def durable_watermark(self) -> int | None:
        """Ticks proven durable (fsynced): everything below this tick id
        survives a crash. None = serving without a WAL."""
        if self._group_wal is not None:
            return self._group_wal.durable_len
        if self._blob_log is not None:
            return len(self._blob_log) if self.durability == "sync" else 0
        return None

    @property
    def acked_watermark(self) -> int | None:
        """The watermark client acks actually gate on: local durability
        alone without a replication plane, ``min(durable, replicated)``
        with one — an ack then proves the op survives the HOST, not
        just the process. The plane ships synchronously on the WAL
        writer thread, so in the healthy case the two watermarks move
        together and the pipelined tick hides the commit round trip; a
        partitioned quorum freezes the replicated side and acks stay
        withheld (clients resend — the degraded-WAL discipline)."""
        dw = self.durable_watermark
        if dw is not None and self.replication is not None:
            dw = min(dw, self.replication.replicated_len)
        return dw

    @property
    def _staged_ns(self) -> dict:
        """Ingress decode / admission ns spent on buffered frames since
        the last ledger record (the next record holds them; a frame shed
        before it is cut takes its share back)."""
        pending = self.ledger.pending()
        return {s: pending[s] for s in ("ingress_decode", "admission")}

    def _stage(self, name: str, tick: int | None = None):
        """The ledger stage ``name`` on live traffic; nothing during WAL
        replay (reconstruction is not serving)."""
        if self._replay:
            return _NO_STAGE
        return self.ledger.stage(name, tick)

    def _drain_durable_acks(self) -> None:
        """Push withheld acks whose tick the WAL has fsynced (and the
        follower quorum journaled, when replication is attached) —
        called on the serving thread (harvest / forced flush), never
        the writer thread, so session pushes stay single-threaded."""
        dw = self._group_wal.durable_len
        if self.replication is not None:
            dw = min(dw, self.replication.replicated_len)
        if not self._unacked or self._unacked[0][0] >= dw:
            return
        if self._inflight:
            # Chaos kill class "fsync-complete-before-readback": tick N
            # is durable and about to ack while a later tick's device
            # work is still in flight (its readback not yet taken).
            # Recovery must replay N byte-identically and must never
            # treat the in-flight tick as acked or durable.
            faults.crashpoint("storm.overlap_fsynced")
        with self._stage("ack_send"):
            while self._unacked and self._unacked[0][0] < dw:
                _tick, acks, t_harvested, led = self._unacked.pop(0)
                t_drain = time.monotonic_ns()
                if led is not None:
                    # The tick's commit-wait: harvest done → fsync
                    # watermark passed (the acked-durable latency the
                    # ledger attributes).
                    self.ledger.amend(led, "wal_commit_wait",
                                      t_drain - t_harvested)
                faults.crashpoint("storm.pre_ack")
                for frame, payload in acks:
                    payload["dw"] = dw
                    if frame.trace is not None:
                        self.tracer.mark(frame.trace, "durable", t_drain)
                        self._stamp_trace_ack(frame, payload)
                    if frame.t0:
                        # Per-tenant SLO surface: submit→durable-ack
                        # latency into the tenant's ack histogram
                        # (get_metrics exports p50/p99; render_tenants
                        # renders them).
                        self.qos.observe_ack(frame.tenant,
                                             (t_drain - frame.t0) / 1e9)
                    frame.push(payload)

    def _sync_wal_stats(self) -> None:
        wal = self._group_wal
        if wal is not None:
            self.stats["wal_fsync_ns"] = wal.fsync_ns
            self.stats["wal_batches"] = wal.batches

    def _push_synth_acks(self, acks: list, mega_plans: dict) -> None:
        """Deliver acks for a cohort that collapsed to zero descs (every
        entry decided zero-op by the mega combiner). Nothing sequenced —
        but a refseq outcome journaled a state-bearing mark CONTROL
        record, and the client acts on the nack (rebases, advances its
        resend window), so the acked-before-durable discipline applies
        here too: barrier the group commit before pushing. A degraded
        WAL withholds these acks exactly like tick acks (the client
        retries; live and recovered decisions are deterministic either
        way)."""
        with self._stage("ack_send"):
            from ..protocol.codec import StormAck
            if self._group_wal is not None and not self._replay:
                from .durable_store import WalDegradedError
                try:
                    self._group_wal.sync()
                except WalDegradedError:
                    return  # not durable: withhold (clients resend)
                if self.replication is not None \
                        and self.replication.replicated_len \
                        < self._group_wal.durable_len:
                    # Durable locally but not on the follower quorum: the
                    # same withhold discipline, one tier out.
                    return
            dw = self.acked_watermark
            for ack_i, (frame, _i0, _i1) in enumerate(acks):
                if frame.push is None:
                    continue
                plan = mega_plans.get(ack_i) or []
                rows = np.asarray([v for kind, v in plan if kind == "s"],
                                  np.int32).reshape(-1, 4)
                payload = StormAck(frame.rid, rows)
                payload["dw"] = dw
                if frame.trace is not None:
                    self._stamp_trace_ack(frame, payload)
                frame.push(payload)

    def _stamp_trace_ack(self, frame: _Frame, payload: dict) -> None:
        """Finish a sampled frame's span at ack transmit: the joined hop
        marks ride the ack header ("tc" + "hops", monotonic ns — clients
        on the same host join their send/rx clocks in), the hop deltas
        feed ``storm.hop.*`` histograms, and the span record goes out
        through the telemetry logger."""
        self.tracer.mark(frame.trace, "ack_tx")
        span = self.tracer.finish(frame.trace)
        if span is None:
            return
        payload[TRACE_KEY] = frame.trace[0]  # the client's raw id
        payload["hops"] = span["hops"]
        metrics = self.merge_host.metrics
        for name, ms in span["deltas_ms"].items():
            metrics.histogram(f"storm.hop.{name}").observe(ms / 1000.0)

    def _flush_round(self, require_full: bool = False) -> bool:
        """One fused tick over every buffered frame, deferring repeat
        frames for the same document to the next round (one descriptor
        per doc row per tick). With ``require_full``, a round whose
        DISJOINT doc set falls short of the tick threshold declines
        (returns False) — pipelined senders whose later ticks arrive
        early must not fragment the cohort into undersized device ticks."""
        if self.wal_degraded and not self._replay:
            # Breaker open: do NOT advance device state ahead of a WAL
            # that cannot journal it — frames stay queued (new ones are
            # already nacked at _admit) and at most the in-flight
            # pipeline's few ticks still need WAL appends, so the
            # bounded group-commit queue can never overflow into the
            # harvest path mid-outage.
            return False
        if (self.replication is not None and not self._replay
                and not self.replication.quorum_ok):
            # Quorum lost: a tick here would advance device state and
            # journal records no quorum can replicate — the acks would
            # park anyway, and history past the replicated watermark is
            # exactly what a promoted incarnation forks away. Frames
            # stay buffered in arrival order (per-doc FIFO preserved),
            # so the healed quorum sequences the identical history a
            # never-partitioned leader would have.
            self.merge_host.metrics.gauge("repl.parked_docs").set(
                self._pending_docs)
            return False
        queue_depth = self._pending_docs
        frames, self._frames, self._pending_docs = self._frames, [], 0
        # Bus-path ops already admitted must sequence first (per-doc total
        # order is shared between the storm and per-op paths). The
        # in-round flag keeps the pump's idle pass from evicting docs out
        # from under the cohort being assembled (residency.evict refuses
        # while it is set).
        with self._stage("service_pump"):
            self._in_round = True
            try:
                self.service.pump()
                self.seq_host._flush_pending()
            finally:
                self._in_round = False

        # Tick composition is the QoS seam (server/qos.py): the deficit
        # round robin drains per-tenant queues by weight into the tick's
        # doc slots — an abusive tenant saturates only its own share.
        # The plan keeps the two hard ordering rules: one frame per doc
        # per tick (per-doc FIFO — a colliding frame stays buffered),
        # and the mega FIFO fence (once any frame of a promoted doc is
        # passed over, every LATER frame of that doc is too — the
        # combiner stamps doc seqs in cohort order, and taking a later
        # lane's frame past a deferred earlier one would reorder the
        # doc's total order relative to the single-lane path). A
        # single-tenant compose with no slot budget reduces exactly to
        # the legacy first-come scan.
        with self._stage("cohort"):
            if self._replay:
                # Replay never re-composes: the recorded cohort IS the
                # composition (one frame per replayed tick), and scheduler
                # state comes from the tick headers — a synthetic replay
                # frame must not register phantom tenants.
                qplan = {"selected": frames, "kept": [], "charge": {},
                         "slices": {}, "quantum": None}
            else:
                qplan = self.qos.compose(frames, self.tick_slot_budget)
            selected: list[_Frame] = qplan["selected"]
            kept: list[_Frame] = qplan["kept"]
            # A slot budget below the flush threshold caps every cohort
            # under it — a full-budget tick IS a full round then, or
            # threshold-triggered flushing would decline forever.
            full_bar = self.flush_threshold_docs \
                if self.tick_slot_budget is None \
                else min(self.flush_threshold_docs, self.tick_slot_budget)
            if require_full and sum(len(f.docs) for f in selected) \
                    < full_bar:
                # Undersized cohort: put everything back; the idle drain (or
                # the cohort completing) will run it. No mega decision has
                # run yet and the scheduler plan was NOT committed, so
                # re-buffering is side-effect free.
                self._frames = frames + self._frames
                self._pending_docs += sum(len(f.docs) for f in frames)
                return False
            self.qos.commit(qplan)
            if not self._replay:
                # Chaos kill class "mid-composition": scheduler state moved
                # (deficits charged, rotation advanced) but the tick neither
                # dispatched nor journaled. Recovery restores the scheduler
                # from the last durable tick's header; the selected frames
                # come back via client resend and recompose deterministically
                # against that state — the single-tenant twin diff proves
                # fairness never changes converged replica state.
                faults.crashpoint("storm.qos_mid_compose")
            self._frames.extend(kept)
            self._pending_docs += sum(len(f.docs) for f in kept)
            if not self._replay:
                self.qos.reset_pending(self._frames)
        # HARVEST-FIRST (the round-14 pipelining order): settle the due
        # tick BEFORE staging this one, so its readback is taken the
        # moment it matters and its WAL append reaches the writer thread
        # NOW — the group fsync then runs concurrent with this round's
        # scatter + device dispatch instead of queueing behind them
        # (BENCH_r10 measured the two stages back-to-back at 0.52 + 0.41
        # of every durable tick). This also frees the harvested tick's
        # staging generation for reuse below, and it must precede the
        # mega cohort transform: the combiner may journal CONTROL
        # records, which have to land AFTER the due tick's WAL record so
        # replay re-applies mirror updates in live order.
        while len(self._inflight) >= max(1, self.pipeline_depth):
            self._harvest_one(self._inflight.pop(0))
        with self._stage("cohort"):
            # WAL replay re-runs the tick with its RECORDED timestamp so the
            # sequencer planes (client last_update) rebuild byte-identically.
            # Computed before cohort assembly: the mega combiner stamps the
            # same clock the device ts plane carries.
            now = (self._replay_ts if self._replay_ts is not None
                   else self.service._clock())
            descs: list[tuple[str, str, int, int, int]] = []
            frame_words: list[np.ndarray] = []   # one payload view per frame
            frame_counts: list[np.ndarray] = []
            metas: list[np.ndarray] = []
            acks: list[tuple[_Frame, int, int]] = []  # frame -> desc [i0, i1)
            mega_rows: dict[int, tuple] = {}   # desc idx -> doc-space quad
            mega_plans: dict[int, list] = {}   # ack idx -> per-entry plan
            for frame in selected:
                i0 = len(descs)
                if frame.mega is not None and not self._replay:
                    # The combiner: doc-space tickets in cohort admission
                    # order (== the single-lane interleaving), dup prefixes
                    # trimmed out of the words, zero-op entries dropped with
                    # synthesized ack rows.
                    (fdesc, fwords, fcounts, fmeta, plan,
                     desc_rows) = self.megadoc.decide_frame(frame, now)
                    descs.extend(fdesc)
                    frame_words.append(fwords)
                    frame_counts.append(fcounts)
                    metas.append(fmeta)
                    for rel, row in enumerate(desc_rows):
                        if row is not None:
                            mega_rows[i0 + rel] = row
                    if len(fdesc) != len(frame.docs):
                        # Dropped entries: the ack is rebuilt positionally
                        # from this plan (synth row or kept-desc index).
                        mega_plans[len(acks)] = [
                            ("s", item.synth) if item.synth is not None
                            else ("l", i0 + item.desc_rel)
                            for item in plan]
                else:
                    descs.extend(frame.docs)
                    frame_words.append(frame.words)
                    frame_counts.append(frame.counts)
                    metas.append(frame.meta)
                acks.append((frame, i0, len(descs)))
            if not descs:
                # Every selected entry resolved to a zero-op outcome: no
                # tick to ride — deliver the synthesized acks now (nothing
                # was sequenced, so there is no durability to wait on; the
                # one state-bearing zero-op outcome journaled its own
                # control record in decide_frame).
                self._push_synth_acks(acks, mega_plans)
                return True
            if self._replay and self.megadoc is not None:
                # Replayed lane entries are already cleaned: rebuild the
                # combiner's mirrors + combine logs in desc order (== the
                # order live decisions ran).
                self.megadoc.replay_decide(descs, now)
            if self.megadoc is not None and not self._replay:
                self.megadoc.finish_cohort(descs)
        if not self._replay:
            self._traced_pending = 0  # next round gets a fresh cap
        with self._stage("scatter"):
            seq_host, merge_host = self.seq_host, self.merge_host
            desc_arr = metas[0] if len(metas) == 1 else np.concatenate(metas)
            counts_col = desc_arr[:, 2]
            k = _next_pow2(int(counts_col.max()))

            # Rows + slots (the only per-doc Python work on the hot path).
            # Storm cohorts repeat tick after tick (the same docs stream
            # frames continuously), so the resolved arrays are cached keyed
            # on the exact (doc, client) sequence and the sequencer's
            # membership generation (any join/leave/restore invalidates).
            cohort_key = (seq_host.membership_gen,
                          tuple((d, c) for d, c, *_ in descs))
            cached = self._cohort_cache.get(cohort_key)
            if cached is not None:
                seq_rows, slots, map_rows, mrows, lane_rows = cached
            else:
                seq_rows = np.empty(len(descs), np.int32)
                slots = np.empty(len(descs), np.int32)
                map_rows = np.empty(len(descs), np.int32)
                mrows = []
                for i, (doc, client, _cseq0, _ref, _count) in enumerate(descs):
                    row = seq_host._row(doc)
                    seq_rows[i] = row
                    slots[i] = seq_host._slots[row].get(client,
                                                        seq_host._ghost)
                    mrow = self._storm_mrow(doc)
                    map_rows[i] = mrow.row
                    mrows.append(mrow)
                # Lane sub-sequencer rows keep their cref planes pinned at 0
                # (the doc-space refseq/MSN law lives in the mega combiner);
                # cached alongside the cohort so the per-round forcing below
                # is one vectorized store, not a per-desc string scan.
                lane_rows = (self.megadoc.lane_seq_rows(descs, seq_rows)
                             if self.megadoc is not None
                             else np.empty(0, np.int32))
                self._cohort_cache.put(cohort_key,
                                       (seq_rows, slots, map_rows, mrows,
                                        lane_rows))

            b_seq = seq_host._capacity
            b_map = merge_host._map_capacity
            # Double-buffered staging generations: this round scatters into
            # the IDLE generation while the one a still-in-flight tick's
            # device transfer may alias stays untouched (pipeline_depth + 1
            # generations rotate round-robin; the harvest-first loop above
            # guarantees the generation coming up for reuse was harvested
            # ≥ one round ago). The per-doc vectors re-zero (cheap memsets);
            # the [B, K] words plane deliberately does NOT: every window the
            # tick consumes lies inside the [0, count) prefix freshly
            # scattered for its row this round (rows without a batch have
            # count 0 and an empty ticket window), so stale words from the
            # generation's previous tick are unreachable by construction and
            # the ~MB-scale memset stays off the hot path.
            gen = self._staging_gen(b_seq, b_map, k)
            slot_full = gen["slot"]
            cseq0_full = gen["cseq0"]
            ref_full = gen["ref"]
            seq_counts = gen["seq_counts"]
            ts_full = gen["ts"]
            ts_full.fill(now)
            words_full = gen["words"]
            map_counts = gen["map_counts"]
            gather = gen["gather"]
            slot_full[seq_rows] = slots
            cseq0_full[seq_rows] = desc_arr[:, 0]
            ref_full[seq_rows] = desc_arr[:, 1]
            if lane_rows.size:
                # Live metas already carry 0 here (megadoc._meta_for); the
                # REPLAY path rebuilds metas from WAL entries, whose ref
                # column is the doc-space ref the records need — force the
                # device feed back to the lane contract either way.
                ref_full[lane_rows] = 0
            seq_counts[seq_rows] = desc_arr[:, 2]
            map_counts[map_rows] = desc_arr[:, 2]
            gather[map_rows] = seq_rows
            if counts_col.min() == counts_col.max() == k:
                # Uniform storm (the common shape): one fancy-index scatter
                # PER FRAME, reading straight from each frame's receive
                # buffer (a reshape view) — no np.stack copy, no per-doc
                # Python loop between the socket and the device staging.
                pos = 0
                for fw, fc in zip(frame_words, frame_counts):
                    n = len(fc)
                    words_full[map_rows[pos:pos + n]] = fw.reshape(n, k)
                    pos += n
            else:
                pos = 0
                for fw, fc in zip(frame_words, frame_counts):
                    off = 0
                    for n in fc.tolist():
                        words_full[map_rows[pos], :n] = fw[off:off + n]
                        off += n
                        pos += 1

            seq_host._host_state = None  # device state is about to move
        with self._stage("device_dispatch") as dispatch:
            (seq_host._state, merge_host._xstate, n_seq, first, last,
             msn, bad, kstats) = _storm_tick(
                seq_host._state, merge_host._xstate,
                jnp.asarray(slot_full), jnp.asarray(cseq0_full),
                jnp.asarray(ref_full), jnp.asarray(ts_full),
                jnp.asarray(seq_counts), jnp.asarray(gather),
                jnp.asarray(words_full), jnp.asarray(map_counts))
            # Chaos kill class "mid-tick": device state mutated, durable
            # record NOT yet enqueued — the mutation is volatile and must be
            # reconstructible from snapshot + WAL replay + client resend.
            faults.crashpoint("storm.mid_tick")
            # Pipeline: enqueue this tick's device work (and start its
            # device→host copies), then harvest only what has ≥ depth later
            # ticks already in flight behind it.
            rec = dict(
                descs=descs, frame_words=frame_words, counts=counts_col,
                map_rows=map_rows, mrows=mrows,
                acks=acks, now=now, submitted=int(counts_col.sum()),
                out=(n_seq, first, last, msn, bad, kstats),
                depth=self.pipeline_depth, queue_depth=queue_depth,
                mega_rows=mega_rows or None, mega_plans=mega_plans or None,
                # Scheduler state AS OF this tick's composition (harvest may
                # run rounds later under pipelining — the WAL header must
                # journal the state the tick was composed against, so replay
                # restores it at the identical point) + the per-tenant slot
                # slices for the windowed attribution ring.
                qos_state=(None if self.qos.is_trivial()
                           else self.qos.export_state()),
                qos_slices=qplan["slices"] or None)
            for out_arr in rec["out"]:
                copy_async = getattr(out_arr, "copy_to_host_async", None)
                if copy_async is not None:
                    copy_async()
        if not self._replay:
            for frame, _i0, _i1 in acks:
                if frame.trace is not None:
                    self.tracer.mark(frame.trace, "dispatch", dispatch.t1)
        self._inflight.append(rec)
        if self._group_wal is not None and not self._replay:
            # Chaos kill class "mid-overlap dispatch": this tick's device
            # work is enqueued while the previous tick's group commit may
            # still be in flight on the writer thread. The previous tick
            # must replay byte-identically from whatever the WAL made
            # durable, and THIS tick (never appended, never acked) must
            # come back only via client resend.
            faults.crashpoint("storm.overlap_dispatch")
        if self.pipeline_depth == 0:
            # Serial fallback: settle this tick NOW — readback, WAL
            # append, the full durability barrier (measured inline as
            # its commit-wait stage) and its acks — before anything else
            # may stage. The conservative pre-pipelining shape (and the
            # A/B twin the pipelined path diffs against).
            self._harvest_one(self._inflight.pop(0))
        elif self._group_wal is not None and self._unacked \
                and not self._replay:
            # Opportunistic NON-blocking drain: a tick whose fsync
            # completed while this round staged and dispatched acks now
            # instead of waiting for the next harvest — the client-side
            # flow-control window is keyed off these acks, so releasing
            # them a round late would stall windowed senders a full
            # cadence.
            self._drain_durable_acks()
        return True

    def _staging_gen(self, b_seq: int, b_map: int, k: int) -> dict:
        """The next idle host staging generation. ``pipeline_depth + 1``
        generations rotate round-robin, so the arrays this round
        scatters into are NEVER ones a still-in-flight tick's device
        transfer may alias (jax.Array transfers on some backends keep a
        view of the host buffer until the computation consumes it) —
        a frame scattered into generation B while generation A is in
        flight must never touch A's device feed. A geometry change
        (capacity growth, a different per-round K) reallocates just the
        generation it lands on; a runtime pipeline_depth change resizes
        the ring."""
        n = self.pipeline_depth + 1
        if len(self._staging) != n:
            self._staging = [None] * n
            self._staging_idx = 0
        self._staging_idx = (self._staging_idx + 1) % n
        gen = self._staging[self._staging_idx]
        if gen is None or gen["shape"] != (b_seq, b_map, k):
            gen = {
                "shape": (b_seq, b_map, k),
                "slot": np.zeros(b_seq, np.int32),
                "cseq0": np.zeros(b_seq, np.int32),
                "ref": np.zeros(b_seq, np.int32),
                "ts": np.zeros(b_seq, np.int32),
                "seq_counts": np.zeros(b_seq, np.int32),
                "words": np.zeros((b_map, k), np.uint32),
                "map_counts": np.zeros(b_map, np.int32),
                "gather": np.zeros(b_map, np.int32),
            }
            self._staging[self._staging_idx] = gen
        else:
            # Re-zero the per-doc vectors only — the words plane's stale
            # content is unreachable (see the _flush_round comment).
            for f in ("slot", "cseq0", "ref", "seq_counts", "map_counts",
                      "gather"):
                gen[f].fill(0)
        return gen

    def idle_drain(self) -> bool:
        """Bounded, NON-blocking idle-path service (the bridge pump's
        no-event branch): release acks whose group commit completed, run
        buffered partial-cohort tails, and harvest an in-flight tick
        whose device results are already materialized. Unlike
        :meth:`flush`, this never blocks on the durability barrier and
        never collapses the pipeline — a flow-controlled client waiting
        out its ack window goes quiet between frames, and forcing a full
        settle on every quiet poll would serialize the server back into
        lockstep dispatch→fsync ticks. Returns True when anything
        progressed."""
        moved = False
        if self._group_wal is not None and self._unacked:
            before = len(self._unacked)
            self._drain_durable_acks()
            moved = len(self._unacked) != before
        if self._frames:
            # A partial tail below the tick threshold: the senders are
            # BLOCKED on these acks (nothing else is coming) — the full
            # settle is the right shape here, exactly as before.
            self.flush()
            return True
        if self._inflight:
            ready = all(
                getattr(arr, "is_ready", lambda: True)()
                for arr in self._inflight[0]["out"])
            if ready:
                self._harvest_one(self._inflight.pop(0))
                moved = True
        if not self._inflight and self._unacked \
                and self._group_wal is not None:
            # Pipeline EMPTY, only the group commit outstanding: a
            # lockstep (ack-gated, unwindowed) sender is blocked on
            # exactly this fsync, and there is nothing in flight the
            # barrier could serialize against — take it, bounded by one
            # group-commit latency, and release the acks now instead of
            # next poll.
            from .durable_store import WalDegradedError
            try:
                self._group_wal.sync()
            except WalDegradedError:
                pass  # degraded: acks stay withheld until healed
            else:
                self._drain_durable_acks()
                moved = True
        return moved

    def _harvest(self) -> None:
        while self._inflight:
            self._harvest_one(self._inflight.pop(0))

    def _harvest_one(self, rec: dict) -> None:
        tick_id = self._tick_counter
        with self._stage("readback", tick_id) as readback:
            n_seq, first, last, msn, bad, kstats = (np.asarray(a)
                                                    for a in rec["out"])
            # Device-side kernel counters (the i32[3] stats plane riding this
            # readback): sequenced / dup-dropped / sentinel docs, device-true.
            kstats = kstats.tolist()
        with self._stage("ack_pack", tick_id):
            if self._group_wal is not None and not self._replay:
                # Chaos kill class "readback-before-fsync": this tick's
                # results are read back but its durable record has not yet
                # reached the writer thread — the whole tick is volatile and
                # must be reconstructible from snapshot + WAL replay +
                # client resend (nothing of it was ever acked).
                faults.crashpoint("storm.readback_pre_wal")
            map_rows = rec["map_rows"]
            # ONE batched gather+pack builds the tick's per-doc ack matrix
            # (n_seq, first, last, msn) — the columnar twin of
            # pack_map_words; the WAL-header lists and every frame's ack are
            # derived from it (columnar → Python exactly once; int() per
            # device element inside the doc loop would dominate the harvest).
            ack_rows = np.stack(
                (n_seq[map_rows], first[map_rows], last[map_rows],
                 msn[map_rows]), axis=1).astype(np.int32, copy=False)
            ns_l = ack_rows[:, 0].tolist()
            fs_l = ack_rows[:, 1].tolist()
            ls_l = ack_rows[:, 2].tolist()
            m_l = ack_rows[:, 3].tolist()
            bad_rows = bad[map_rows]
            any_bad = bool(bad_rows.any())
            bad_l = bad_rows.tolist()
            if not self._replay:
                for frame, _i0, _i1 in rec["acks"]:
                    if frame.trace is not None:
                        self.tracer.mark(frame.trace, "sequenced",
                                         readback.t1)
            fanout = self.service.fanout
            viewers = getattr(self.service, "viewers", None)
            if viewers is not None and (self._replay
                                        or not viewers.active_rooms):
                viewers = None
            # Desc indices whose docs have viewer rooms — collected inside
            # the one existing per-desc loop (no second O(descs) pass).
            # Rooms key by the PARENT doc for mega-lane descs (viewer frames
            # must keep flowing while a doc is promoted), publishing the
            # combiner's DOC-space quad instead of the lane-space device row.
            viewer_idx: list[int] = []
            viewer_rooms: dict[int, str] = {}
            megadoc = self.megadoc
            mega_rows_all = rec.get("mega_rows") or {}
            now = rec["now"]
            mrows = rec["mrows"]
            # scriptorium tick record: ONE blob per tick — a json header of
            # every document's columnar record followed by the raw words.
            # RAM keeps only a compact (first_seq, last_seq, tick) triplet
            # per (doc, tick), so the serving host's memory stays bounded by
            # the tick RATE it retains, not the op volume (with a spill dir
            # the blob rides the disk oplog — the Mongo-storage analog).
            self._tick_counter += 1
            # The WAL words region is the frames' receive-buffer views,
            # appended as-is; per-doc byte offsets are one cumsum, not a loop.
            counts_col = rec["counts"]
            word_parts: list = rec["frame_words"]
            total_seq = int(sum(ns_l))
            w_offs = np.zeros(len(counts_col), np.int64)
            w_offs[1:] = np.cumsum(counts_col[:-1].astype(np.int64) * 4)
            offsets = w_offs.tolist()
            header_docs = []
            replaying = self._replay
            doc_tick_counts = self.doc_tick_counts
            pubs: list = [] if fanout is not None and not replaying else None
            for i, (doc, client, cseq0, ref, count) in enumerate(rec["descs"]):
                ns, fs, ls, m = ns_l[i], fs_l[i], ls_l[i], m_l[i]
                mrow = mrows[i]
                if ls > mrow.last_seq:
                    mrow.last_seq = ls
                header_docs.append([doc, client, cseq0, ref, count,
                                    ns, fs, ls, m, offsets[i]])
                if not replaying:
                    if ns > 0:
                        dt = self._doc_ticks.setdefault(doc, [])
                        dt.append((fs, ls, tick_id))
                        retention = self.doc_index_retention_ticks
                        if retention is not None and dt[0][2] < (
                                tick_id - retention):
                            # Opt-in index retention (see __init__): drop
                            # entries below the horizon; ticks are appended
                            # in order, so the trim is a prefix cut.
                            horizon = tick_id - retention
                            keep = 0
                            while keep < len(dt) and dt[keep][2] < horizon:
                                keep += 1
                            del dt[:keep]
                    # Telemetry for the quarantine blast-radius invariant:
                    # batch peers of a quarantined doc lose zero ticks.
                    doc_tick_counts[doc] = doc_tick_counts.get(doc, 0) + 1
                    if any_bad and bad_l[i] and doc not in self.quarantined:
                        self._quarantine_doc(doc, "sentinel", tick_id)
                    # broadcaster: compact tick frame into the pub/sub hop.
                    if pubs is not None:
                        pubs.append((doc, b"\x00storm%d:%d:%d" % (fs, ls, m)))
                    if viewers is not None and ns > 0:
                        room_doc = doc
                        if megadoc is not None:
                            parent = megadoc.parent_of(doc)
                            if parent is not None:
                                room_doc = parent
                        if viewers.has_viewers(room_doc):
                            viewer_idx.append(i)
                            viewer_rooms[i] = room_doc
        with self._stage("fanout_publish", tick_id):
            if pubs:
                # O(batch) broadcast: the whole tick's room publishes go down
                # in ONE native call (fanout_publish_batch) — never one
                # Python write per subscriber connection.
                batch_pub = getattr(fanout, "publish_batch", None)
                if batch_pub is not None:
                    batch_pub(pubs)
                else:  # duck-typed fanout without the batch surface
                    for room, body in pubs:
                        fanout.publish(room, body)
            # Viewer plane: docs with viewer rooms get this tick's broadcast
            # frame (sequenced window + raw words) serialized ONCE per doc
            # and fanned out in one batched publish — encodes-per-tick ==
            # hot docs with viewers, independent of viewer count (the
            # serialize-once invariant BENCH_r13 pins). Words resolve
            # straight from each frame's receive-buffer view (the same
            # positional layout the WAL appends); only frames CONTAINING a
            # viewer doc pay an offsets walk — a 10k-doc tick with one
            # viewer room touches one frame, not every desc.
            if viewer_idx:
                import bisect
                frame_words = rec["frame_words"]
                items = []
                for f_idx, (_frame, i0, i1) in enumerate(rec["acks"]):
                    lo = bisect.bisect_left(viewer_idx, i0)
                    hi = bisect.bisect_left(viewer_idx, i1)
                    if lo == hi:
                        continue  # no viewer docs in this frame
                    fcounts = counts_col[i0:i1].tolist()
                    target = viewer_idx[lo]
                    off = 0
                    for local, count in enumerate(fcounts):
                        gi = i0 + local
                        if gi == target:
                            words = frame_words[f_idx][off:off + count]
                            # Lane descs broadcast the combiner's doc-space
                            # quad (the same rewrite the client ack gets);
                            # viewers of a promoted doc see continuous doc
                            # seq windows, never lane-space ones.
                            quad = mega_rows_all.get(gi) or (
                                ns_l[gi], fs_l[gi], ls_l[gi], m_l[gi])
                            items.append((viewer_rooms[gi], quad[0],
                                          quad[1], quad[2], quad[3],
                                          count, words.tobytes()))
                            lo += 1
                            if lo == hi:
                                break
                            target = viewer_idx[lo]
                        off += count
                viewers.publish_ticks(items)
        with self._stage("wal_append", tick_id):
            import json as _json
            import struct as _struct

            hdr: dict = {"v": STORM_WAL_VERSION, "ts": now,
                         "docs": header_docs}
            if rec.get("qos_state") is not None:
                # Multi-tenant scheduler state as of this tick's composition
                # (single-tenant headers stay byte-compatible with every
                # pre-QoS reader/golden — the field simply never appears).
                # Replay imports it tick by tick, so a recovered host's
                # deficits equal the crashed host's at the durable frontier.
                hdr["qos"] = rec["qos_state"]
            header = _json.dumps(hdr, separators=(",", ":")).encode()
            prefix = _struct.pack("<I", len(header)) + header
            if self._replay:
                pass  # the blob IS the replay source; never re-persist it
            elif self._group_wal is not None:
                # Async group commit: the hot path pays ONE queue put; the
                # join + CRC + append + fsync all run on the writer thread.
                # The tick's acks are withheld until the durability watermark
                # passes it (_drain_durable_acks) — the sync per-tick blob
                # write this replaces was the round-5 regression suspect.
                idx = self._group_wal.append([prefix, *word_parts])
                assert idx == tick_id, (idx, tick_id)
                if self.pipeline_depth == 0:
                    # Serial fallback: the durability barrier is tick time
                    # ON this thread — nothing overlaps it — so it is
                    # timed directly as the commit-wait stage and the
                    # tick's wall-clock slice covers it (no amend-at-drain;
                    # the ledger must never report phantom overlap for a
                    # genuinely sequential tick).
                    from .durable_store import WalDegradedError
                    with self._stage("wal_commit_wait", tick_id):
                        try:
                            self._group_wal.sync()
                        except WalDegradedError:
                            # Breaker open: acks stay withheld (not
                            # durable); _admit already sheds new writes.
                            self.merge_host.metrics.counter(
                                "storm.degraded_flushes").inc()
            elif self._blob_log is not None:
                blob_bytes = prefix + b"".join(
                    bytes(memoryview(p)) for p in word_parts)
                idx = self._blob_log.append(blob_bytes)
                assert idx == tick_id, (idx, tick_id)
                if self.durability == "sync":
                    with self._stage("wal_commit_wait", tick_id):
                        self._blob_log.sync()
            else:
                self._tick_blobs[tick_id] = prefix + b"".join(
                    bytes(memoryview(p)) for p in word_parts)
        # Stats BEFORE acks: once an ack leaves the process, this host's
        # bookkeeping must already reflect the tick (clients/tests react
        # to acks immediately).
        self.stats["ticks"] += 1
        self.stats["sequenced_ops"] += total_seq
        self.stats["nacked_or_ignored_ops"] += rec["submitted"] - total_seq
        self._sync_wal_stats()
        # Storm ops are serving-path device ops: count them in the merge
        # host's routing stats so scalar_fraction spans BOTH ingest paths.
        self.merge_host.stats["device_ops"] += total_seq
        self.merge_host.metrics.counter("storm.sequenced_ops").inc(total_seq)
        # Device-true counters from the kstats plane (vs the host-derived
        # stats above — a drift between the two is itself a signal).
        kmetrics = self.merge_host.metrics
        kmetrics.counter("storm.device.sequenced_ops").inc(kstats[0])
        kmetrics.counter("storm.device.dup_ops").inc(kstats[1])
        kmetrics.counter("storm.device.sentinel_docs").inc(kstats[2])
        # Block-table rebalance attribution (KSTAT_REBALANCE_FIRED /
        # KSTAT_BLOCKS_TOUCHED): 0 on this map-only path by layout; the
        # counters move wherever the mixed/text tick harvests through
        # the same indices, and tools/monitor.py renders the fire rate.
        kmetrics.counter("storm.device.rebalance_fired").inc(
            kstats[KSTAT_REBALANCE_FIRED])
        kmetrics.counter("storm.device.blocks_touched").inc(
            kstats[KSTAT_BLOCKS_TOUCHED])
        if not replaying and rec.get("qos_slices"):
            # Per-tenant slice of this tick: doc slots from the compose
            # plan, sequenced ops from the harvested ack matrix — the
            # windowed share attribution render_tenants reads.
            seq_by_t: dict[str, int] = {}
            for frame, i0, i1 in rec["acks"]:
                seq_by_t[frame.tenant] = seq_by_t.get(frame.tenant, 0) \
                    + int(sum(ns_l[i0:i1]))
            self.qos.note_tick(tick_id, rec["qos_slices"], seq_by_t)
        # Mega combiner egress: lane descs' device rows carry LANE-space
        # seqs (what the WAL header above recorded — reads translate);
        # the CLIENT sees doc-space quads, pre-decided by the combiner.
        # The device count must agree with the decision (cleaned lane
        # batches sequence in full by construction) — a drift here means
        # the lane contract broke, which must fail loudly, not misack.
        mega_rows_rec = rec.get("mega_rows")
        if mega_rows_rec:
            if not replaying:
                self.megadoc.note_harvest(rec["descs"])
            for gi, row in mega_rows_rec.items():
                if ns_l[gi] != row[0]:
                    raise AssertionError(
                        f"mega lane desc {rec['descs'][gi][:2]} sequenced "
                        f"{ns_l[gi]} ops on device, combiner decided "
                        f"{row[0]}")
                ack_rows[gi] = row
        elif self.megadoc is not None and not replaying:
            self.megadoc.note_harvest(rec["descs"])
        # Each frame's ack is a contiguous row slice of the tick's ack
        # matrix — a StormAck that session push paths binary-encode
        # without ever building per-doc dicts. Frames the mega transform
        # shrank rebuild their rows positionally from the plan
        # (synthesized zero-op quads interleaved with harvested rows).
        with self._stage("ack_pack", tick_id) as packed:
            from ..protocol.codec import StormAck
            mega_plans = rec.get("mega_plans") or {}
            acks = []
            for ack_i, (frame, i0, i1) in enumerate(rec["acks"]):
                if frame.push is None:
                    continue
                plan = mega_plans.get(ack_i)
                if plan is None:
                    payload = StormAck(frame.rid, ack_rows[i0:i1])
                else:
                    rows = np.empty((len(plan), 4), np.int32)
                    for j, (kind, v) in enumerate(plan):
                        rows[j] = v if kind == "s" else ack_rows[v]
                    payload = StormAck(frame.rid, rows)
                if any_bad and bad_rows[i0:i1].any():
                    # The tick's sequencing is durable and correct (the
                    # ticket is exact; the poison is in the served planes) —
                    # the ack stands, but the client learns its doc is
                    # frozen: further submits nack until readmission.
                    payload["quarantined"] = [
                        rec["descs"][i][0] for i in range(i0, i1) if bad_l[i]]
                    payload["retry_after_s"] = self.busy_retry_s
                acks.append((frame, payload))
        # Commit the tick's ledger record (fixed shape; replay ticks are
        # reconstruction, not serving — they don't pollute attribution).
        # Group-mode commit-wait is unknown until the fsync watermark
        # passes the tick; the drain backfills it on the record object.
        led = None
        if not self._replay:
            # The tick's wall-clock slice is the harvest-to-harvest
            # cadence: every serving-thread stage timed since the
            # previous harvest lands in this record, so wall minus their
            # sum is what no stage covered. The commit-wait amended in
            # later ran on the writer thread, under the next tick's
            # stages; attribution() reports it as overlap_ms instead of
            # double-counting it.
            led = self.ledger.record(tick_id, rec.get("queue_depth", 0),
                                     len(rec["descs"]), rec["submitted"],
                                     wall_ns=None,
                                     depth=rec.get("depth",
                                                   self.pipeline_depth))
        if self._group_wal is not None and not self._replay:
            # Withhold until fsynced — then deliver in tick order with the
            # durability watermark stamped on (clients resubmit anything
            # above the watermark after a reconnect). The serial fallback
            # already measured its inline barrier as wal_commit_wait, so
            # its record must NOT be amended at drain (led=None there).
            self._unacked.append((tick_id, acks, packed.t1,
                                  led if self.pipeline_depth > 0
                                  else None))
            self._drain_durable_acks()
        elif acks:
            dw = self.durable_watermark
            with self._stage("ack_send", tick_id):
                t_ack_tx = time.monotonic_ns()
                for frame, payload in acks:
                    faults.crashpoint("storm.pre_ack")
                    payload["dw"] = dw
                    if frame.trace is not None:
                        self._stamp_trace_ack(frame, payload)
                    if frame.t0:
                        self.qos.observe_ack(frame.tenant,
                                             (t_ack_tx - frame.t0) / 1e9)
                    frame.push(payload)

    # -- snapshot / recovery ---------------------------------------------------
    #
    # The crash-consistency pair (ISSUE 4 tentpole): checkpoint() writes a
    # device-pool snapshot (sequencer rows + merge-host pools + the WAL
    # tick watermark) to the content-addressed snapshot store; recover()
    # restores the head and replays the WAL tail THROUGH THE SERVING TICK
    # itself (same fused program, recorded timestamps), so a restarted
    # controller reconverges byte-identically with an uninterrupted twin.
    # tools/chaos.py kills the process at every dangerous point and
    # proves exactly that.

    SNAPSHOT_DOC = "__storm__"

    def checkpoint(self) -> str:
        """Settle everything (harvest + durability barrier), then publish
        one snapshot atomically: upload first, flip the head ref last —
        a crash mid-checkpoint leaves the previous head intact."""
        assert self.snapshots is not None, "no snapshot store attached"
        if self.replication is not None and self.replication.fenced:
            # A demoted leader's snapshot would clobber the promoted
            # incarnation's head — the zombie-writes hazard fencing
            # exists to stop.
            raise RuntimeError(
                "checkpoint() on a fenced (demoted) leader; the "
                f"promoted incarnation {self.replication.moved_to!r} "
                "owns the snapshot head")
        if self.wal_degraded:
            from .durable_store import WalDegradedError
            raise WalDegradedError(
                "checkpoint() while the WAL fsync breaker is open: the "
                "snapshot watermark cannot barrier on durability")
        if self.quarantined:
            # A snapshot taken now would capture the quarantined docs'
            # POISONED device rows — and readmit_doc rebuilds from the
            # snapshot head, so the poison would become the rebuild
            # source and the freeze unliftable. Readmit first.
            raise RuntimeError(
                f"checkpoint() with quarantined docs "
                f"{sorted(self.quarantined)}: readmit them first (a "
                "snapshot would capture their poisoned rows)")
        self._in_checkpoint = True
        try:
            self.flush()
            if self.wal_degraded:
                # Re-check AFTER the flush: the breaker may have opened
                # during it (flush swallows the barrier failure to keep
                # serving) — publishing now would stamp a tick_watermark
                # the WAL never made durable.
                from .durable_store import WalDegradedError
                raise WalDegradedError(
                    "WAL fsync breaker opened during the checkpoint "
                    "flush; snapshot watermark would not be durable")
            if self.quarantined:
                # Same re-check for quarantine: the settle flush itself
                # may have tripped the sentinel, and the poisoned row
                # must never become a rebuild source.
                raise RuntimeError(
                    f"sentinel quarantined {sorted(self.quarantined)} "
                    "during the checkpoint flush; readmit before "
                    "snapshotting")
            import dataclasses
            snap = {
                "kind": "storm-checkpoint",
                "format_version": STORM_SNAPSHOT_VERSION,
                "tick_watermark": self._tick_counter,
                "sequencer": {
                    doc: dataclasses.asdict(cp)
                    for doc, cp in self.seq_host.checkpoint_all().items()},
                "merge_host": self.merge_host.export_state(),
            }
            if self.megadoc is not None and self.megadoc.docs:
                # Lane DEVICE rows already ride checkpoint_all (lane ids
                # are sequencer docs) and the merge-host export; this is
                # the combiner's host state (mirrors + combine logs).
                snap["megadoc"] = self.megadoc.export_state()
            if not self.qos.is_trivial():
                # Fair-composition state (deficits + rotation): restored
                # at recover() and then rolled forward by the WAL tail's
                # per-tick "qos" headers — deficit counters survive
                # restarts exactly like the cohort machinery.
                snap["qos"] = self.qos.export_state()
            if self.history is not None and self.history.branches:
                # Branch registry (summaries and cold seeds are already
                # store-resident under their own heads).
                snap["history"] = self.history.export_state()
            handle = self.snapshots.upload(self.SNAPSHOT_DOC, snap)
            faults.crashpoint("snapshot.pre_publish")
            self.snapshots.set_head(self.SNAPSHOT_DOC, handle)
            self._last_checkpoint_tick = self._tick_counter
            if self.replication is not None:
                # Replica-side WAL retention: the snapshot watermark is
                # the followers' trim floor (recovery never replays
                # below it); the plane names the sub-floor ticks still
                # live here so follower reads stay byte-identical.
                self.replication.ship_retention(self._last_checkpoint_tick)
            return handle
        finally:
            self._in_checkpoint = False

    def recover(self) -> dict:
        """Restore the snapshot head (when one exists) into the sequencer
        and merge hosts, then replay the WAL ticks past the snapshot's
        watermark. Call once on a FRESH controller stack, before serving.
        Without a snapshot the durable tick history is still readable
        (the __init__ scan) but live state starts empty — the per-op tier
        then rebuilds from the bus/scriptorium replay instead."""
        assert not self._frames and not self._inflight, (
            "recover() on a controller already serving")
        restored_from = None
        start = 0
        if self.snapshots is not None:
            head = self.snapshots.head(self.SNAPSHOT_DOC)
            snap = self.snapshots.get(self.SNAPSHOT_DOC, head)
            if snap is not None:
                version = snap.get("format_version", 0)
                if not 0 <= version <= STORM_SNAPSHOT_VERSION:
                    raise ValueError(
                        f"storm snapshot format v{version} is newer than "
                        f"this reader (max v{STORM_SNAPSHOT_VERSION})")
                from .sequencer import SequencerCheckpoint
                for doc, cp in sorted(snap["sequencer"].items()):
                    self.seq_host.restore(doc, SequencerCheckpoint(**cp))
                self.merge_host.import_state(snap["merge_host"])
                if snap.get("megadoc") is not None:
                    if self.megadoc is None:
                        raise RuntimeError(
                            "snapshot holds mega-doc combiner state but "
                            "no MegaDocManager is attached")
                    self.megadoc.import_state(snap["megadoc"])
                if snap.get("qos") is not None:
                    self.qos.import_state(snap["qos"])
                if snap.get("history") is not None:
                    if self.history is None:
                        raise RuntimeError(
                            "snapshot holds history-plane branch state "
                            "but no HistoryPlane is attached")
                    self.history.import_state(snap["history"])
                start = snap["tick_watermark"]
                restored_from = head
                if self.residency is not None:
                    # Docs the global snapshot restored are resident;
                    # the WAL-tail replay below hydrates cold docs on
                    # first touch (prepare_replay).
                    self.residency.adopt_resident()
            elif self._blob_log is not None and len(self._blob_log) > 0:
                # The WAL holds durable ticks but no snapshot is
                # readable (corrupt head/chunks, or a crash before the
                # first checkpoint). Serving EMPTY live state over a
                # non-empty acked history would silently diverge from
                # what clients already saw — fail loudly instead; the
                # operator restores a snapshot or clears the spill dir.
                raise RuntimeError(
                    f"recover(): WAL holds {len(self._blob_log)} durable "
                    "ticks but no snapshot head is readable; refusing to "
                    "serve empty state over an acked history")
        # Memory-only serving with snapshots: tick ids continue past the
        # watermark (no blob scan set them), so fresh ticks never alias.
        self._tick_counter = max(self._tick_counter, start)
        durable = len(self._blob_log) if self._blob_log is not None else 0
        if self._blob_log is not None and start > durable:
            # Snapshot watermark ahead of the WAL: an unfsynced tail died
            # with the host (possible under durability != "group"; the
            # group mode's checkpoint barrier makes watermark <= durable).
            # The snapshot itself holds the full state at the watermark,
            # but tick ids must stay 1:1 with WAL record indices
            # (_read_blob), so realign by padding empty filler ticks —
            # they carry no docs, so no index or catch-up read ever
            # resolves into them.
            import json as _json
            import struct as _struct
            header = _json.dumps({"ts": 0, "docs": []},
                                 separators=(",", ":")).encode()
            filler = _struct.pack("<I", len(header)) + header
            while len(self._blob_log) < start:
                self._blob_log.append(filler)
            if self._group_wal is not None:
                self._group_wal.sync()
            durable = len(self._blob_log)
        replayed = 0
        if restored_from is not None and start < durable:
            replayed = self._replay_wal(start, durable)
        self._last_checkpoint_tick = self._tick_counter
        if self.residency is not None:
            # Trim the blob-scan index back to the hot set: cold docs'
            # indexes live in their cold snapshots (restored on hydrate).
            self.residency.after_recover()
        return {"restored_from": restored_from, "replayed_ticks": replayed}

    def _replay_wal(self, start: int, end: int) -> int:
        """Re-run ticks [start, end) from their durable blobs through the
        serving path: same cohorts, same recorded timestamps, no
        re-persisting (the blob being replayed IS the durable record)."""
        self._replay = True
        try:
            for tick in range(start, end):
                blob = self._read_blob(tick)
                header, off = self._parse_header(blob)
                if header.get("qos") is not None:
                    # Roll the scheduler forward to the state this tick
                    # was composed against (composition itself is NOT
                    # re-run — the recorded cohort IS the composition).
                    self.qos.import_state(header["qos"])
                mg = header.get("mg")
                if mg is not None:
                    # Mega-doc lifecycle control record: re-apply the
                    # event at the identical point in the total order
                    # (promotion re-seeds from the recovered checkpoint,
                    # demotion re-folds the recovered lanes).
                    if self.megadoc is None:
                        raise RuntimeError(
                            "WAL holds mega-doc control records but no "
                            "MegaDocManager is attached — attach one "
                            "before recover()")
                    self._tick_counter = tick + 1
                    self.megadoc.apply_control(mg, header["ts"])
                    continue
                hp = header.get("hp")
                if hp is not None:
                    # History-plane control record: branch forks re-seed
                    # at the identical point in the total order (the
                    # seed is a pure function of the records below this
                    # tick); trimmed-tick fillers are stateless.
                    self._tick_counter = tick + 1
                    if hp.get("op") == "trimmed" or hp.get("trimmed"):
                        continue
                    if self.history is None:
                        raise RuntimeError(
                            "WAL holds history-plane control records "
                            "but no HistoryPlane is attached — attach "
                            "one before recover()")
                    self.history.apply_control(hp, header["ts"])
                    continue
                self._tick_counter = tick
                self._replay_ts = header["ts"]
                entries = [e[:5] for e in header["docs"]]
                payload = memoryview(blob)[off:]
                if self.residency is not None:
                    # Hydrate cold docs on first touch; drop the entries
                    # a doc's cold snapshot already reflects (ticks
                    # below its watermark) — watermark-exact, per-doc
                    # independent, so peers replay unchanged.
                    kept = self.residency.prepare_replay(entries, tick)
                    if not kept:
                        # Whole tick inside cold snapshots: account for
                        # it without a device tick (ids must stay 1:1
                        # with WAL record indices).
                        self._tick_counter = tick + 1
                        continue
                    if len(kept) != len(entries):
                        # The payload is positional (words located by
                        # cumulative counts), so dropped entries splice
                        # their word slices out too — each header entry
                        # records its byte offset (index 9).
                        w_off = {e[0]: e[9] for e in header["docs"]}
                        payload = memoryview(b"".join(
                            bytes(payload[w_off[doc]:
                                          w_off[doc] + count * 4])
                            for doc, _c, _c0, _r, count in kept))
                    entries = kept
                self._adopt_replay_clients(entries, header)
                self.submit_frame(None, {"docs": entries, "rid": None},
                                  payload)
                self.flush()
        finally:
            self._replay = False
            self._replay_ts = None
        assert self._tick_counter == end, (self._tick_counter, end)
        return end - start

    def _adopt_replay_clients(self, entries: list, header: dict) -> None:
        """A client named by a durable tick header that the restored
        row does not know joined AFTER the restore source (a branch
        fork seed, or a fresh doc created past the last checkpoint —
        membership rides the bus tier, never the storm WAL). Replaying
        its frame against the ghost lane would silently drop ops the
        live tick acked, so adopt the client at its RECORDED dedup
        prefix: ``cseq`` just below the first sequenced op (the header's
        ``count - ns`` dup prefix replays to the identical outcome) and
        ``cref`` at the entry's ref (what the live tick left behind).
        Mega lane ids are skipped — the combiner mirror syncs lane
        membership itself (replay_decide)."""
        rec_by_doc = {e[0]: e for e in header["docs"]}
        for doc, client, cseq0, ref, count in entries:
            if self.megadoc is not None \
                    and self.megadoc.parent_of(doc) is not None:
                continue
            row = self.seq_host._rows.get(doc)
            if row is not None and client in self.seq_host._slots[row]:
                continue
            ns = rec_by_doc[doc][5]
            self.seq_host._row(doc)
            cp = self.seq_host.checkpoint(doc)
            cp.clients.append({
                "client_id": client,
                "client_seq": cseq0 + (count - ns) - 1,
                "ref_seq": ref,
                "last_update": header["ts"],
                "can_evict": True, "can_summarize": True,
                "nack": False,
            })
            self.seq_host.restore(doc, cp)

    # -- per-doc quarantine ----------------------------------------------------
    #
    # The blast-radius tentpole: one poisoned document must never take
    # its batch down. Detection is the device sentinel in _storm_tick
    # (vseq drift / negative planes); _quarantine_doc freezes ONLY the
    # flagged doc — buffered frames touching it nack retryable, new
    # submits shed at _admit, reads serve through the scalar fold of the
    # durable records — and readmit_doc() rebuilds it from the snapshot
    # head + its own WAL tail (exact: storm tickets are per-doc
    # independent) while every other row keeps full-rate serving.

    def _quarantine_doc(self, doc_id: str, reason: str,
                        tick_id: int) -> None:
        self.quarantined[doc_id] = {"reason": reason, "tick": tick_id}
        if self.megadoc is not None:
            # A poisoned LANE freezes the whole promoted doc: submits
            # name the parent (admission checks run pre-rewrite), and a
            # partial freeze would let sibling lanes advance the doc's
            # total order past an unservable range. Readmission of a
            # promoted doc is demote-after-readmit (see module doc).
            parent = self.megadoc.parent_of(doc_id)
            if parent is not None:
                for other in [parent] + self.megadoc.lane_ids(parent):
                    if other not in self.quarantined:
                        self._quarantine_doc(other, reason, tick_id)
        self.stats["quarantined_docs"] += 1
        self.merge_host.metrics.counter("storm.quarantines").inc()
        # In-flight ops: nack every BUFFERED frame touching the doc with
        # a retryable code (the client resubmits after readmission; cseq
        # dedup absorbs any overlap). Frames NOT touching the doc stay
        # queued; a frame sharing it is dropped whole (acks are
        # positional per frame) with every dropped doc listed, so the
        # client resubmits its healthy docs immediately.
        kept: list[_Frame] = []
        for frame in self._frames:
            if not any(d == doc_id for d, *_ in frame.docs):
                kept.append(frame)
                continue
            self._pending_docs -= len(frame.docs)
            # Refund the shed frame's staged ledger ns and trace slot:
            # a tick that never served it must not inherit its
            # attribution, and its sampling-cap slot frees for peers.
            for stage in frame.staged:
                self.ledger.refund(stage)
            if frame.trace is not None:
                self._traced_pending = max(0, self._traced_pending - 1)
            self._shed(frame.push, {"rid": frame.rid},
                       sum(n for *_, n in frame.docs), "quarantined",
                       self.busy_retry_s,
                       docs=[d for d, *_ in frame.docs],
                       quarantined=[doc_id], tenant=frame.tenant)
        self._frames = kept
        self.qos.reset_pending(self._frames)

    def quarantined_map_entries(self, doc_id: str) -> dict:
        """Scalar-engine serving for a quarantined doc: fold the durable
        columnar records (poison-free by construction — the ticket plane
        is exact even when the served planes corrupt) into the converged
        map. The doc stays readable at scalar cost while frozen."""
        from ..dds.map_data import MapData
        if self.history is not None and self.history.tail_floor(doc_id):
            # A compacted+trimmed doc's record prefix is gone — the
            # summary chain is the authoritative base; the history fold
            # serves the same converged entries shape.
            return self.history.read_at(
                doc_id, self.history.head_seq(doc_id))["entries"]
        records = self.records_overlapping(doc_id, 0)
        data = MapData()
        for m in materialize_storm_records(records, self.datastore,
                                           self.channel,
                                           blob_reader=self.read_tick_words):
            data.process(m.contents["contents"]["contents"], False, None)
        return dict(data.items())

    def readmit_doc(self, doc_id: str, verify: bool = True) -> dict:
        """From-snapshot rebuild of ONE quarantined document: restore its
        sequencer row and map row from the snapshot head, replay its WAL
        tail through the serving tick (recorded timestamps, single-doc
        cohorts), verify against the scalar fold, and lift the freeze.
        The rest of the batch serves normally throughout."""
        assert doc_id in self.quarantined, f"{doc_id!r} not quarantined"
        assert self.snapshots is not None, \
            "readmit_doc needs a snapshot store"
        self.flush()  # settle peers; the doc itself has nothing buffered
        head = self.snapshots.head(self.SNAPSHOT_DOC)
        snap = self.snapshots.get(self.SNAPSHOT_DOC, head)
        assert snap is not None, "no readable snapshot head to rebuild from"
        from .sequencer import SequencerCheckpoint
        cp = snap["sequencer"].get(doc_id)
        assert cp is not None, f"snapshot holds no sequencer row for {doc_id}"
        self.seq_host.restore(doc_id, SequencerCheckpoint(**cp))
        self._restore_map_row(doc_id, snap["merge_host"])
        start = snap["tick_watermark"]
        end = saved_counter = self._tick_counter
        replayed = 0
        self._replay = True
        try:
            for tick in range(start, end):
                blob = self._read_blob(tick)
                header, off = self._parse_header(blob)
                for entry in header["docs"]:
                    doc, client, cseq0, ref, count = entry[:5]
                    if doc != doc_id or count <= 0:
                        continue
                    w_off = entry[9]
                    self._tick_counter = tick
                    self._replay_ts = header["ts"]
                    words = memoryview(blob)[off + w_off:
                                             off + w_off + count * 4]
                    self.submit_frame(
                        None, {"docs": [[doc, client, cseq0, ref, count]],
                               "rid": None}, words)
                    self.flush()
                    replayed += 1
                    break
        finally:
            self._replay = False
            self._replay_ts = None
            self._tick_counter = saved_counter
        if verify:
            rebuilt = self.merge_host.map_entries(doc_id, self.datastore,
                                                  self.channel)
            shadow = self.quarantined_map_entries(doc_id)
            assert rebuilt == shadow, (
                f"readmit of {doc_id!r} diverged from the durable-record "
                f"fold: {rebuilt} != {shadow}")
        info = self.quarantined.pop(doc_id)
        self.stats["readmitted_docs"] += 1
        self.merge_host.metrics.counter("storm.readmits").inc()
        return {"doc": doc_id, "reason": info["reason"],
                "replayed_ticks": replayed, "snapshot": head}

    def _restore_map_row(self, doc_id: str, host_snap: dict) -> None:
        """Overwrite the doc's LIVE device map row with its snapshot row
        (or init defaults when the snapshot predates the row) — the map
        half of the per-doc from-snapshot rebuild; peers' rows are
        untouched."""
        live_row = self._storm_map_row(doc_id)
        from .merge_host import _nd_unpack
        m = host_snap["map"]
        snap_row = None
        for rec in m["rows"]:
            if list(rec["key"]) == [doc_id, self.datastore, self.channel]:
                snap_row = rec["row"]
                break
        xs = self.merge_host._xstate
        s_live = xs.present.shape[1]
        vals = {"present": np.zeros(s_live, np.bool_),
                "value": np.zeros(s_live, np.int32),
                "vseq": np.full(s_live, -1, np.int32),
                "cleared_seq": np.int32(-1)}
        if snap_row is not None:
            planes = {f: _nd_unpack(m["planes"][f])
                      for f in mk.MapState._fields}
            s_snap = planes["present"].shape[1]
            assert s_snap <= s_live, (
                f"snapshot map row wider than live ({s_snap} > {s_live})")
            for f in ("present", "value", "vseq"):
                vals[f][:s_snap] = planes[f][snap_row]
            vals["cleared_seq"] = planes["cleared_seq"][snap_row]
        self.merge_host._xstate = mk.MapState(
            **{f: getattr(xs, f).at[live_row].set(vals[f])
               for f in mk.MapState._fields})

    @staticmethod
    def _parse_header(blob: bytes) -> tuple[dict, int]:
        """(header, words byte offset) — no copy of the words region.
        Validates the tick format version: headers without "v" are the
        committed pre-version (v0) format and parse identically; a
        version NEWER than this reader refuses loudly (a rolled-back
        binary must not misparse a newer WAL)."""
        import json as _json
        import struct as _struct

        hlen = _struct.unpack_from("<I", blob)[0]
        header = _json.loads(blob[4:4 + hlen].decode())
        version = header.get("v", 0)
        if not 0 <= version <= STORM_WAL_VERSION:
            raise ValueError(
                f"storm WAL tick format v{version} is newer than this "
                f"reader (max v{STORM_WAL_VERSION})")
        return header, 4 + hlen

    def _read_blob(self, tick_id: int) -> bytes:
        if self._blob_log is not None:
            if (self._group_wal is not None
                    and tick_id >= self._group_wal.durable_len):
                # Catch-up reads ARE durability proof to clients (the
                # DeltaManager watermark contract): a record must never
                # leave this process ahead of its fsync, so reading an
                # in-flight tick barriers the group commit first. Rare
                # (tip readers racing the writer thread) and bounded by
                # one group-commit latency. With the fsync breaker OPEN
                # this raises WalDegradedError rather than waiting out
                # the outage OR serving unfsynced bytes as durable —
                # reads below the watermark keep serving; tip reads fail
                # retryably (the front door answers the request with an
                # error and keeps the socket).
                self._group_wal.sync()
            return bytes(self._blob_log.read(tick_id))
        return self._tick_blobs[tick_id]

    def read_tick_words(self, tick_id: int) -> bytes:
        """Raw words of one harvested tick (scriptorium read path)."""
        blob = self._read_blob(tick_id)
        _header, off = self._parse_header(blob)
        return blob[off:]

    def trim_tick_blobs(self, ticks: set[int]) -> int:
        """Rewrite superseded tick blobs to tiny filler records (the
        history-plane tail trim): tick ids stay 1:1 with WAL positions
        — only the bytes shrink. Callers (HistoryPlane.trim_now) have
        already proven the ticks are below the checkpoint watermark
        (never replayed) and referenced by no live catch-up index. The
        filler still parses as a valid docs-less tick header, so a
        reused spill dir rescans cleanly."""
        if not ticks:
            return 0
        import json as _json
        import struct as _struct
        header = _json.dumps(
            {"v": STORM_WAL_VERSION, "ts": 0, "docs": [],
             "hp": {"op": "trimmed"}}, separators=(",", ":")).encode()
        filler = _struct.pack("<I", len(header)) + header

        def transform(idx: int, data: bytes) -> bytes | None:
            if idx in ticks and len(data) > len(filler):
                return filler
            return None

        if self._group_wal is not None:
            return self._group_wal.rewrite_records(transform)
        if self._blob_log is not None:
            # Plain OpLog spill ("sync"/"none"): the shared atomic
            # rewrite, no writer thread to coordinate with.
            from .durable_store import rewrite_oplog_records
            self._blob_log, changed = rewrite_oplog_records(
                self._blob_log, self._spill_path, transform)
            return changed
        changed = 0
        for tick in ticks:
            blob = self._tick_blobs.get(tick)
            if blob is not None and len(blob) > len(filler):
                self._tick_blobs[tick] = filler
                changed += 1
        return changed

    def records_overlapping(self, doc_id: str, from_seq: int,
                            to_seq: int | None = None) -> list[dict]:
        """Columnar scriptorium records of ``doc_id`` whose seq windows
        overlap (from_seq, to_seq] — resolved from the per-tick blobs via
        the compact in-RAM (first, last, tick) index. The shape matches
        what :func:`materialize_storm_records` consumes. A doc with
        mega-lane history merges its lane records translated to doc seq
        space through the combine logs."""
        if self.megadoc is not None and self.megadoc.has_history(doc_id):
            return self.megadoc.records(doc_id, from_seq, to_seq,
                                        self._records_for)
        return self._records_for(doc_id, from_seq, to_seq)

    def _records_for(self, doc_id: str, from_seq: int,
                     to_seq: int | None = None) -> list[dict]:
        """Untranslated per-id record resolution (lane ids included)."""
        out = []
        ticks = self._doc_ticks.get(doc_id)
        if ticks is None and self.residency is not None \
                and not self.residency.is_resident(doc_id):
            # Cold doc: its catch-up index rode the eviction snapshot.
            # A gap fetch is a READ — serve it from the cold head
            # without hydrating (readers must not churn the pool).
            ticks = self.residency.cold_doc_ticks(doc_id)
        for fs, ls, tick in ticks or ():
            if ls <= from_seq or (to_seq is not None and fs > to_seq):
                continue
            header, _off = self._parse_header(self._read_blob(tick))
            for (doc, client, cseq0, ref, count,
                 ns, hfs, hls, m, w_off) in header["docs"]:
                if doc == doc_id:
                    out.append({
                        "client": client, "first_cseq": cseq0,
                        "ref_seq": ref, "count": count, "n_seq": ns,
                        "first_seq": hfs, "last_seq": hls, "msn": m,
                        "timestamp": header["ts"], "tick": tick,
                        "w_off": w_off,
                    })
                    break
        return out

    def _storm_mrow(self, doc_id: str):
        """The doc's map-row OBJECT (cohort resolution caches it so the
        harvest's last_seq updates never re-key the row dict per doc)."""
        key = ChannelKey(doc_id, self.datastore, self.channel)
        mrow = self.merge_host._map_rows.get(key)
        if mrow is None:
            mrow = self.merge_host._map_row(key)
            mrow.literal_values = True
            # Storm words address keys BY SLOT; pin the canonical names so
            # map_entries/materialization agree (10-bit slot space).
            mrow.key_slots = {f"k{s}": s
                              for s in range(self.merge_host._map_slots)}
        elif not getattr(mrow, "literal_values", False):
            raise ValueError(
                f"channel {key} already serves dict-path ops; storm and "
                "dict traffic cannot mix on one channel")
        return mrow

    def _storm_map_row(self, doc_id: str):
        return self._storm_mrow(doc_id).row


def materialize_storm_records(records: list[dict], datastore: str,
                              channel: str,
                              blob_reader=None
                              ) -> list[SequencedDocumentMessage]:
    """Per-op messages for catch-up readers (the lazy read path of the
    columnar scriptorium records). NACKed/IGNORED ops are omitted — only
    sequenced ops exist in the document's history.

    Records either embed their words (legacy ``"words"`` b64) or
    reference a per-tick blob (``"tick"`` + ``"w_off"`` — the harvest
    writes ONE raw blob per tick); pass the controller's
    :meth:`StormController.read_tick_words` as ``blob_reader`` to
    resolve the latter. Blobs are cached for the duration of the call.

    NOTE: a tick whose ops were partially rejected materializes its
    sequenced ops with consecutive seqs from first_seq (exact when
    rejections are a prefix — the common dup-resend shape)."""
    out: list[SequencedDocumentMessage] = []
    blob_cache: dict[int, bytes] = {}
    for rec in records:
        if rec["n_seq"] <= 0:
            continue
        if "words" in rec:
            words = np.frombuffer(base64.b64decode(rec["words"]),
                                  np.uint32, rec["count"])
        else:
            tick = rec["tick"]
            blob = blob_cache.get(tick)
            if blob is None:
                assert blob_reader is not None, (
                    "tick-blob record needs a blob_reader")
                blob = blob_reader(tick)
                blob_cache[tick] = blob
            words = np.frombuffer(blob, np.uint32, rec["count"],
                                  rec["w_off"])
        skip = rec["count"] - rec["n_seq"]  # rejected prefix (dup resend)
        for j in range(rec["n_seq"]):
            word = int(words[skip + j])
            kind = word & 3
            slot = (word >> 2) & 0x3FF
            value = (word >> 12) & 0xFFFFF
            if kind == mk.MAP_SET:
                contents = {"type": "set", "key": f"k{slot}",
                            "value": value}
            elif kind == mk.MAP_DELETE:
                contents = {"type": "delete", "key": f"k{slot}"}
            else:
                contents = {"type": "clear"}
            out.append(SequencedDocumentMessage(
                client_id=rec["client"],
                sequence_number=rec["first_seq"] + j,
                minimum_sequence_number=rec["msn"],
                client_sequence_number=rec["first_cseq"] + skip + j,
                reference_sequence_number=rec["ref_seq"],
                type=MessageType.OPERATION,
                contents={"address": datastore,
                          "contents": {"address": channel,
                                       "contents": contents}},
                timestamp=rec["timestamp"],
                data=None,
            ))
    return out


__all__ = ["StormController", "materialize_storm_records"]
