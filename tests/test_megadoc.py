"""Mega-doc write scale-out (round 15): one document's merge served
from sharded device lanes.

The differential discipline extends to the new tier: sharded (promoted,
L lanes) ≡ single-lane (unpromoted twin) ≡ scalar (the MapData fold of
the materialized records) must be BYTE-IDENTICAL on live + adversarial
streams — converged entries, per-frame ack quads, materialized op
history (seqs/cseqs/refs/MSNs), and the demoted sequencer checkpoint.
The doc-space combiner itself is pinned against the device closed-form
ticket by its own differential test. Tier-1 runs all of this on the
FORCED multi-device CPU mesh (conftest forces platform + an 8-device
host mesh programmatically before first device use — the
jax.config.update route; the JAX_PLATFORMS env var alone does not stick
in this container), so the sequence-parallel tier is exercised by every
CI run, not only where real devices exist.
"""

from __future__ import annotations

import dataclasses
import os

import numpy as np
import pytest

from fluidframework_tpu.server.kernel_host import KernelSequencerHost
from fluidframework_tpu.server.megadoc import (
    DocSequencerMirror,
    MegaDocManager,
    fold_map_rows,
    lane_of_writer,
)
from fluidframework_tpu.server.merge_host import KernelMergeHost
from fluidframework_tpu.server.routerlicious import RouterliciousService
from fluidframework_tpu.server.storm import (
    StormController,
    materialize_storm_records,
)

K = 6  # ops per frame in the fuzz


def build_stack(tmp_path=None, lanes=None, **storm_kw):
    seq = KernelSequencerHost(num_slots=2, initial_capacity=4)
    mh = KernelMergeHost(flush_threshold=10**9)
    kwargs = {}
    if tmp_path is not None:
        from fluidframework_tpu.server.durable_store import (
            DurableMessageBus,
            FileStateStore,
            GitSnapshotStore,
        )
        kwargs["bus"] = DurableMessageBus(os.path.join(tmp_path, "bus"))
        kwargs["store"] = FileStateStore(os.path.join(tmp_path, "state"))
        storm_kw.setdefault("spill_dir", os.path.join(tmp_path, "spill"))
        storm_kw.setdefault("durability", "group")
        storm_kw.setdefault(
            "snapshots", GitSnapshotStore(os.path.join(tmp_path, "git")))
    svc = RouterliciousService(merge_host=mh, batched_deli_host=seq,
                               auto_pump=False, idle_check_interval=10**9,
                               **kwargs)
    svc._clock = lambda: 5  # deterministic ts: clu planes must compare
    storm = StormController(svc, seq, mh, flush_threshold_docs=10**9,
                            **storm_kw)
    mgr = MegaDocManager(storm, default_lanes=lanes) if lanes else None
    return svc, storm, seq, mh, mgr


def storm_words(seed, r, w, k=K, slots=16):
    rng = np.random.default_rng([seed, r, w])
    kinds = rng.choice([0, 0, 0, 1], size=k).astype(np.uint32)
    kslots = rng.integers(0, slots, k).astype(np.uint32)
    vals = rng.integers(0, 1 << 20, k).astype(np.uint32)
    return (kinds | (kslots << 2) | (vals << 12)).astype(np.uint32)


# -- the combiner's scalar ticket vs the device closed form -------------------


def test_mirror_matches_device_storm_tickets():
    """DocSequencerMirror is an EXACT scalar twin of storm_tickets:
    random batches (fresh / dup / overlap / gap / stale-ref) through
    both, every outcome and every client plane equal."""
    import jax.numpy as jnp

    from fluidframework_tpu.ops import sequencer as seqk

    rng = np.random.default_rng(11)
    n_clients = 3
    state = seqk.init_state(1, n_clients)
    # Join the clients the way a sequenced CLIENT_JOIN leaves the row.
    state = state._replace(
        active=state.active.at[0, :].set(True),
        cref=state.cref.at[0, :].set(0))
    mirror = DocSequencerMirror()
    for c in range(n_clients):
        # Adoption IS join-at-msn semantics; msn is 0 here, matching
        # the cref=0 the device join left.
        mirror.adopt(f"c{c}", 1, clu=0)
    next_cseq = [1] * n_clients
    for step in range(80):
        c = int(rng.integers(n_clients))
        kind = rng.choice(["fresh", "dup", "overlap", "gap", "stale"],
                          p=[0.55, 0.15, 0.1, 0.1, 0.1])
        n = int(rng.integers(1, 5))
        if kind == "fresh":
            cseq0 = next_cseq[c]
        elif kind == "dup":
            cseq0 = max(1, next_cseq[c] - n)
        elif kind == "overlap":
            cseq0 = max(1, next_cseq[c] - 1)
        elif kind == "gap":
            cseq0 = next_cseq[c] + 2
        else:
            cseq0 = next_cseq[c]
        ref = 0 if kind == "stale" else int(rng.integers(1, 4))
        ts = 100 + step
        state, dups, n_seq, msn = seqk.storm_tickets(
            state, jnp.asarray([c]), jnp.asarray([cseq0]),
            jnp.asarray([ref]), jnp.asarray([ts]), jnp.asarray([n]))
        dec = mirror.decide(f"c{c}", cseq0, ref, n, ts)
        assert dec.n_seq == int(np.asarray(n_seq)[0]), (step, kind)
        assert dec.msn == int(np.asarray(msn)[0]), (step, kind)
        assert mirror.seq == int(np.asarray(state.seq)[0]), (step, kind)
        for cc in range(n_clients):
            w = mirror.writers[f"c{cc}"]
            assert w.cseq == int(np.asarray(state.cseq)[0, cc]), (step, cc)
            assert w.ref == int(np.asarray(state.cref)[0, cc]), (step, cc)
            assert w.nack == bool(np.asarray(state.cnack)[0, cc]), (step,
                                                                    cc)
        assert mirror.last_sent_msn == int(
            np.asarray(state.last_sent_msn)[0])
        # Track what the client would resend next (sequenced advances).
        if dec.n_seq > 0:
            next_cseq[c] = cseq0 + n
    assert mirror.seq > 0  # the stream actually sequenced work


# -- the serving-level differential fuzz --------------------------------------


def _adversarial_frames(seed, writers, rounds):
    """Per-(round, writer) frame plans: mostly fresh contiguous batches,
    plus verbatim dup resends, partial-overlap resends, gaps (NACK), and
    one stale-ref (refseq-below-MSN mark; the marked client retires, as
    the device contract dictates)."""
    rng = np.random.default_rng(seed)
    plans = []
    cseqs = {w: 1 for w in range(writers)}
    prev = {}
    stale_used = False
    for r in range(rounds):
        row = []
        for w in range(writers):
            action = rng.choice(["fresh", "fresh", "fresh", "dup",
                                 "overlap", "gap", "stale"])
            words = storm_words(seed, r, w)
            if action == "dup" and w in prev:
                cseq0, words = prev[w]
                ref = 1
            elif action == "overlap" and w in prev and cseqs[w] > K:
                p_cseq0, p_words = prev[w]
                cseq0 = p_cseq0 + K - 2
                words = np.concatenate([p_words[-2:], words])[:K + 2]
                cseqs[w] = cseq0 + len(words)
                ref = 1
            elif action == "gap":
                cseq0 = cseqs[w] + 3
                ref = 1  # whole batch gap-rejected; cseq unchanged
            elif action == "stale" and not stale_used and r > 1:
                stale_used = True
                cseq0 = cseqs[w]
                ref = 0  # below MSN once anything sequenced -> mark
            else:
                cseq0 = cseqs[w]
                cseqs[w] = cseq0 + K
                ref = 1
                prev[w] = (cseq0, words)
            row.append((w, cseq0, ref, words))
        plans.append(row)
    return plans


def _play(plans, writers, mega_lanes):
    svc, storm, seq, mh, mgr = build_stack(lanes=mega_lanes)
    doc = "hot"
    clients = {w: svc.connect(doc, lambda m: None).client_id
               for w in range(writers)}
    svc.pump()
    if mega_lanes:
        mgr.promote(doc, lanes=mega_lanes)
    acks = {}
    for r, row in enumerate(plans):
        for w, cseq0, ref, words in row:
            storm.submit_frame(
                lambda p, key=(r, w): acks.__setitem__(key, p),
                {"rid": f"{r}-{w}",
                 "docs": [[doc, clients[w], int(cseq0), int(ref),
                           len(words)]]},
                memoryview(np.ascontiguousarray(words).tobytes()))
        storm.flush()
    storm.flush()
    if mega_lanes:
        entries = mgr.map_entries(doc)
        mgr.demote(doc)
        assert mh.map_entries(doc, storm.datastore, storm.channel) \
            == entries  # the demotion fold IS the promoted read
    else:
        entries = mh.map_entries(doc, storm.datastore, storm.channel)
    recs = storm.records_overlapping(doc, 0)
    history = [(m.sequence_number, m.client_sequence_number, m.client_id,
                m.minimum_sequence_number, m.reference_sequence_number,
                repr(m.contents["contents"]["contents"]))
               for m in materialize_storm_records(
                   recs, storm.datastore, storm.channel,
                   blob_reader=storm.read_tick_words)]
    cp = dataclasses.asdict(seq.checkpoint(doc))
    ack_rows = {key: np.asarray(a.rows).tolist() for key, a in acks.items()}
    # Scalar oracle: fold the materialized history through the scalar
    # MapData state machine — converged entries must agree.
    from fluidframework_tpu.dds.map_data import MapData
    data = MapData()
    for m in materialize_storm_records(recs, storm.datastore,
                                       storm.channel,
                                       blob_reader=storm.read_tick_words):
        data.process(m.contents["contents"]["contents"], False, None)
    assert dict(data.items()) == entries
    return entries, ack_rows, history, cp, storm.stats["ticks"]


@pytest.mark.parametrize("seed", [0, 1])
def test_sharded_equals_single_lane_equals_scalar(seed):
    """THE acceptance bar: promoted (L lanes) ≡ unpromoted ≡ scalar,
    byte-identical converged entries / ack quads / materialized history
    / demoted checkpoint on live + adversarial streams — and the
    promoted run takes FEWER ticks (the write path genuinely widened)."""
    writers, rounds, lanes = 5, 6, 2
    plans = _adversarial_frames(100 + seed, writers, rounds)
    e1, a1, h1, cp1, t1 = _play(plans, writers, mega_lanes=None)
    e2, a2, h2, cp2, t2 = _play(plans, writers, mega_lanes=lanes)
    assert e1 == e2
    assert a1 == a2
    assert h1 == h2
    assert cp1 == cp2
    assert t2 < t1, (t2, t1)  # lanes combined writer frames into ticks


def test_zero_op_outcomes_synthesize_identical_acks():
    """Gap / dup / stale-ref frames never touch a lane; their
    synthesized ack quads equal the single-lane device quads (including
    the refseq mark's MSN) — covered broadly by the fuzz, pinned
    narrowly here."""
    writers = 2
    plans = [
        [(0, 1, 1, storm_words(1, 0, 0)), (1, 1, 1, storm_words(1, 0, 1))],
        [(0, 1 + K, 1, storm_words(1, 1, 0)),   # fresh
         (1, 1, 1, storm_words(1, 0, 1))],      # verbatim dup resend
        [(0, 1 + 2 * K, 0, storm_words(1, 2, 0)),  # stale ref -> mark
         (1, 1 + K + 5, 1, storm_words(1, 2, 1))],  # gap -> reject
    ]
    e1, a1, h1, cp1, _ = _play(plans, writers, mega_lanes=None)
    e2, a2, h2, cp2, _ = _play(plans, writers, mega_lanes=2)
    assert (e1, a1, h1, cp1) == (e2, a2, h2, cp2)
    # The dup and the gap really did zero-op (n_seq == 0 quads).
    assert a1[(1, 1)][0][0] == 0
    assert a1[(2, 1)][0][0] == 0
    assert a1[(2, 0)][0][0] == 0  # the stale-ref mark


# -- multi-lane CPU mesh smoke (the tier-1 satellite) -------------------------


def test_sharded_tier_runs_on_forced_multidevice_mesh(cpu_mesh_devices):
    """Tier-1 exercises the sequence-parallel tier on the FORCED
    8-device CPU mesh (programmatic jax.config platform override +
    host-device-count flag set before first device use — see conftest;
    the env-var-only route hangs in this container). One promoted doc's
    text row serves from a mesh-sharded pool and stays byte-identical
    to the unpromoted twin through promote -> serve -> demote."""
    import random

    import jax

    from fluidframework_tpu.ops.mergetree_sharded import make_seg_mesh
    from tests.test_mergetree import get_string, make_string_doc, random_edit

    assert len(jax.devices()) >= 8, "forced host mesh missing"
    mesh = make_seg_mesh(cpu_mesh_devices)

    def play(promote: bool) -> tuple[str, dict]:
        from fluidframework_tpu.server.local_server import LocalCollabServer
        host = KernelMergeHost(merge_slots=16, seg_mesh=mesh,
                               sharded_slot_threshold=4096)
        server = LocalCollabServer(merge_host=host)
        c1 = make_string_doc(server, "mega")
        rng = random.Random(9)
        for _ in range(40):
            random_edit(rng, get_string(c1))
        host.flush()
        key = next(iter(host._merge_rows))
        if promote:
            host.promote_merge_row(key)
            assert host.is_mega_row(key)
            row = host._merge_rows[key]
            devices = {s.device for s in
                       row.pool.state.length.addressable_shards}
            assert len(devices) == len(cpu_mesh_devices)
        for _ in range(30):
            random_edit(rng, get_string(c1))
        host.flush()
        text_mid = host.text("mega", "default", "text")
        if promote:
            assert host.demote_merge_row(key)
            assert not host.is_mega_row(key)
            assert host.text("mega", "default", "text") == text_mid
        for _ in range(10):
            random_edit(rng, get_string(c1))
        host.flush()
        return host.text("mega", "default", "text"), dict(host.stats)

    t_twin, _ = play(False)
    t_mega, stats = play(True)
    assert t_mega == t_twin
    assert stats["megadoc_promotions"] == 1
    assert stats["megadoc_demotions"] == 1


# -- auto promotion / demotion ------------------------------------------------


def test_auto_promotion_and_idle_demotion():
    svc, storm, seq, mh, mgr = build_stack(lanes=2)
    mgr.writer_threshold = 3
    mgr.writer_window_ticks = 1
    mgr.demote_idle_ticks = 3
    hot, cold = "hot", "side"
    hclients = {w: svc.connect(hot, lambda m: None).client_id
                for w in range(3)}
    sclient = svc.connect(cold, lambda m: None).client_id
    svc.pump()
    cseqs = {w: 1 for w in range(3)}
    for r in range(2):
        for w in range(3):
            storm.submit_frame(None, {
                "rid": f"{r}{w}",
                "docs": [[hot, hclients[w], cseqs[w], 1, K]]},
                memoryview(storm_words(5, r, w).tobytes()))
            cseqs[w] += K
        storm.flush()
    assert mgr.is_promoted(hot)  # the writer window crossed the bar
    # Idle: only the side doc ticks from here — the hot doc cools and
    # demotes after demote_idle_ticks harvests.
    sq = 1
    for r in range(8):
        storm.submit_frame(None, {
            "rid": f"s{r}", "docs": [[cold, sclient, sq, 1, K]]},
            memoryview(storm_words(6, r, 0).tobytes()))
        sq += K
        storm.flush()
        if not mgr.is_promoted(hot):
            break
    assert not mgr.is_promoted(hot)
    assert mgr.has_history(hot)  # records still translate
    m = mh.metrics
    assert m.counter("megadoc.promotions").value == 1
    assert m.counter("megadoc.demotions").value == 1


# -- durable lifecycle: snapshot + WAL replay ---------------------------------


def test_recover_replays_promoted_lifecycle(tmp_path):
    """Crash after promoted serving: a fresh stack over the same spill
    dir restores the snapshot (combiner mirrors + lane rows included),
    replays the WAL tail (control records re-promote at the identical
    point), and converges to the live run's entries and combiner
    state."""
    writers = 4
    d = str(tmp_path)
    svc, storm, seq, mh, mgr = build_stack(d, lanes=2)
    doc = "hot"
    clients = {w: svc.connect(doc, lambda m: None).client_id
               for w in range(writers)}
    svc.pump()
    storm.checkpoint()
    mgr.promote(doc, lanes=2)
    cseqs = {w: 1 for w in range(writers)}
    for r in range(3):
        for w in range(writers):
            storm.submit_frame(None, {
                "rid": f"{r}{w}",
                "docs": [[doc, clients[w], cseqs[w], 1, K]]},
                memoryview(storm_words(8, r, w).tobytes()))
            cseqs[w] += K
        storm.flush()
    storm.checkpoint()  # snapshot WITH the promoted combiner state
    for r in range(3, 5):
        for w in range(writers):
            storm.submit_frame(None, {
                "rid": f"{r}{w}",
                "docs": [[doc, clients[w], cseqs[w], 1, K]]},
                memoryview(storm_words(8, r, w).tobytes()))
            cseqs[w] += K
        storm.flush()
    live_entries = mgr.map_entries(doc)
    live_state = mgr.export_state()
    storm._group_wal.close()

    svc2, storm2, seq2, mh2, mgr2 = build_stack(d, lanes=2)
    info = storm2.recover()
    assert info["restored_from"] is not None
    assert info["replayed_ticks"] > 0
    assert mgr2.map_entries(doc) == live_entries
    assert mgr2.export_state() == live_state
    mgr2.demote(doc)
    assert mh2.map_entries(doc, storm2.datastore, storm2.channel) \
        == live_entries
    storm2._group_wal.close()


def test_residency_refuses_evicting_promoted_doc(tmp_path):
    from fluidframework_tpu.server.residency import (
        EvictionRefused,
        ResidencyManager,
    )
    svc, storm, seq, mh, mgr = build_stack(str(tmp_path), lanes=2)
    res = ResidencyManager(storm, max_resident=8, idle_evict_s=1e9,
                           hydration_rate_per_s=1e9)
    doc = "hot"
    client = svc.connect(doc, lambda m: None).client_id
    svc.pump()
    storm.checkpoint()
    mgr.promote(doc, lanes=2)
    storm.submit_frame(None, {"rid": 0, "docs": [[doc, client, 1, 1, K]]},
                       memoryview(storm_words(9, 0, 0).tobytes()))
    storm.flush()
    with pytest.raises(EvictionRefused, match="mega-promoted"):
        res.evict(doc)
    mgr.demote(doc)
    storm._group_wal.close()


# -- the cross-lane fold ------------------------------------------------------


def test_fold_map_rows_delete_and_clear_semantics():
    """Tombstones and clears fold exactly like the single-lane LWW law:
    the latest EVENT wins; a delete winner renders absent; clears erase
    everything older across every lane."""
    def src(present, value, vseq, cleared=-1):
        return {"present": np.asarray(present, bool),
                "value": np.asarray(value, np.int64),
                "vseq": np.asarray(vseq, np.int64),
                "cleared_seq": cleared}

    # Lane B's delete (vseq 7) beats lane A's older set (vseq 3).
    fold = fold_map_rows([
        src([True, True], [10, 20], [3, 5]),
        src([False, False], [0, 0], [7, -1]),
    ])
    assert fold["present"].tolist() == [False, True]
    assert fold["value"].tolist() == [0, 20]
    # A clear at doc seq 6 in lane B erases lane A's older sets but not
    # its newer one.
    fold = fold_map_rows([
        src([True, True], [10, 20], [3, 9]),
        src([False, False], [0, 0], [-1, -1], cleared=6),
    ])
    assert fold["present"].tolist() == [False, True]
    assert fold["value"].tolist() == [0, 20]


def test_lane_of_writer_is_stable():
    assert lane_of_writer("client-1", 4) == lane_of_writer("client-1", 4)
    lanes = {lane_of_writer(f"client-{i}", 4) for i in range(64)}
    assert lanes == set(range(4))  # the hash actually spreads writers


def test_refnack_mark_control_orders_after_inflight_ticks(tmp_path):
    """pipeline_depth=2 regression: a refseq mark decided while an
    earlier tick is still IN FLIGHT must journal its control record
    AFTER that tick's WAL record (the combiner settles the pipeline
    before appending), or replay applies the mark ahead of ops it
    logically followed and the recovered mirror diverges."""
    d = str(tmp_path)
    svc, storm, seq, mh, mgr = build_stack(d, lanes=2, pipeline_depth=2)
    doc = "hot"
    c1 = svc.connect(doc, lambda m: None).client_id
    c2 = svc.connect(doc, lambda m: None).client_id
    svc.pump()
    storm.checkpoint()
    mgr.promote(doc, lanes=2)
    # c1 holds the MSN at 1, c2 refs ahead at 2 — so tick A below MOVES
    # the MSN, and the mark's captured value depends on whether tick A
    # was applied before it (the ordering under test).
    for rid, c, ref in ((0, c1, 1), (1, c2, 2)):
        storm.submit_frame(None, {"rid": rid,
                                  "docs": [[doc, c, 1, ref, K]]},
                           memoryview(storm_words(21, rid, 0).tobytes()))
    storm.flush()
    assert mgr.docs[doc].mirror.msn == 1
    # Tick A: c1 re-refs at 2 (MSN 1 -> 2), dispatches, and STAYS in
    # flight (depth 2: the harvest-first loop settles nothing yet).
    storm.submit_frame(None, {"rid": 2,
                              "docs": [[doc, c1, 1 + K, 2, K]]},
                       memoryview(storm_words(22, 0, 0).tobytes()))
    storm._flush_round()
    assert storm._inflight, "tick A should still be in flight"
    assert mgr.docs[doc].mirror.msn == 2
    # Stale-ref frame from c2 (1 < MSN 2): the refnack mark captures
    # cref = MSN = 2 — but only if tick A's record precedes it on
    # replay.
    storm.submit_frame(None, {"rid": 3,
                              "docs": [[doc, c2, 1 + K, 1, K]]},
                       memoryview(storm_words(22, 1, 0).tobytes()))
    storm._flush_round()
    storm.flush()
    live_state = mgr.export_state()
    assert live_state["docs"][doc]["mirror"]["writers"][c2][3] == 1  # nacked
    storm._group_wal.close()
    svc2, storm2, seq2, mh2, mgr2 = build_stack(d, lanes=2,
                                                pipeline_depth=2)
    storm2.recover()
    assert mgr2.export_state() == live_state
    storm2._group_wal.close()


def test_same_cohort_refnack_mark_replays_identically(tmp_path):
    """Same-COHORT ordering regression: a refseq mark journals BEFORE
    its cohort's tick record, yet an earlier frame in that very cohort
    may have moved the MSN the mark captured. The mark control is
    self-describing (it carries the captured cref), so replay lands the
    exact live value regardless of position."""
    from fluidframework_tpu.server.megadoc import lane_of_writer

    d = str(tmp_path)
    svc, storm, seq, mh, mgr = build_stack(d, lanes=2)
    doc = "hot"
    clients = [svc.connect(doc, lambda m: None).client_id
               for _ in range(4)]
    svc.pump()
    storm.checkpoint()
    mgr.promote(doc, lanes=2)
    c1 = clients[0]
    c2 = next(c for c in clients[1:]
              if lane_of_writer(c, 2) != lane_of_writer(c1, 2))
    # Round 0: EVERY writer sequences (an idle writer's join-time cref
    # would pin the MSN at 0); c1 refs at 1 and becomes the MSN holder,
    # everyone else at 2.
    for i, c in enumerate(clients):
        storm.submit_frame(None, {
            "rid": i, "docs": [[doc, c, 1, 1 if c == c1 else 2, K]]},
            memoryview(storm_words(31, i, 0).tobytes()))
    storm.flush()
    assert mgr.docs[doc].mirror.msn == 1
    # ONE cohort: c1 re-refs at 2 (MSN 1 -> 2) and c2 sends a stale
    # ref 1 — decided in the same _flush_round, distinct lanes.
    storm.submit_frame(None, {"rid": 10,
                              "docs": [[doc, c1, 1 + K, 2, K]]},
                       memoryview(storm_words(32, 0, 0).tobytes()))
    storm.submit_frame(None, {"rid": 11,
                              "docs": [[doc, c2, 1 + K, 1, K]]},
                       memoryview(storm_words(32, 1, 0).tobytes()))
    storm.flush()
    live = mgr.export_state()
    w2 = live["docs"][doc]["mirror"]["writers"][c2]
    assert (w2[1], w2[3]) == (2, 1)  # marked at the POST-c1 MSN of 2
    live_entries = mgr.map_entries(doc)
    storm._group_wal.close()
    svc2, storm2, seq2, mh2, mgr2 = build_stack(d, lanes=2)
    storm2.recover()
    assert mgr2.export_state() == live
    assert mgr2.map_entries(doc) == live_entries
    storm2._group_wal.close()


# -- round-16 satellites: viewer frames, combine-log trim, re-promotion --------


def _mega_serve(storm, doc, writers, rounds, r0=0, ref=-1):
    """One frame per writer per round through the promoted tier
    (``ref=-1`` rides the head so the doc MSN advances — the trim
    horizon's input)."""
    for r in range(r0, r0 + rounds):
        for w, client in enumerate(writers):
            storm.submit_frame(None, {
                "rid": f"{r}.{w}",
                "docs": [[doc, client, 1 + r * K, ref, K]]},
                memoryview(storm_words(11, r, w).tobytes()))
        storm.flush()


def test_viewer_frames_keyed_by_parent_for_promoted_doc():
    """ISSUE 13 satellite: viewer rooms key by the PARENT doc at
    harvest, so per-tick viewer frames KEEP flowing for a promoted doc
    (they used to pause — lane ids never matched the room) and carry
    the combiner's doc-space windows, continuous across lanes."""
    from fluidframework_tpu.protocol.codec import (
        decode_storm_push,
        is_storm_body,
    )
    from fluidframework_tpu.server.broadcaster import ViewerPlane

    svc, storm, seq, mh, mgr = build_stack(lanes=2)
    plane = ViewerPlane(svc)
    doc = "mega-viewer"
    writers = [svc.connect(doc, lambda m: None).client_id
               for _ in range(2)]
    svc.pump()
    events = []

    def push(p):
        if isinstance(p, (bytes, bytearray, memoryview)) \
                and is_storm_body(bytes(p)):
            events.append(decode_storm_push(bytes(p)))

    plane.join(doc, push)
    mgr.promote(doc, lanes=2)
    encodes0 = plane.stats["tick_encodes"]
    rounds = 4
    _mega_serve(storm, doc, writers, rounds)
    ticks = [e for e in events if e.get("event") == "storm_tick"]
    # Frames flowed (one encode per LANE batch per tick — L>1 means
    # several doc-space windows per tick, never zero).
    assert len(ticks) == 2 * rounds
    assert plane.stats["tick_encodes"] - encodes0 == 2 * rounds
    assert all(t["doc"] == doc for t in ticks)
    # Doc-space continuity: the windows tile the doc's op seq range
    # with no lane-space aliasing and the MSN column is doc-space.
    seqs = sorted(s for t in ticks
                  for s in range(t["first"], t["last"] + 1))
    assert seqs == list(range(seqs[0], seqs[0] + 2 * rounds * K))


def test_combine_log_trim_bounds_memory_with_exact_reads():
    """ISSUE 13 satellite (ROADMAP mega residue): with
    ``trim_combine_logs`` armed, a long promotion's per-lane segment
    lists stay bounded by the collab window instead of growing one
    segment per combined batch — while converged reads stay EXACT
    (equal to an untrimmed twin serving the same frames) and catch-up
    below the horizon fails with the reload-from-snapshot contract."""
    doc = "mega-trim"

    def play(trim):
        svc, storm, seq, mh, mgr = build_stack(lanes=2)
        mgr.trim_combine_logs = trim
        writers = [svc.connect(doc, lambda m: None).client_id
                   for _ in range(2)]
        svc.pump()
        mgr.promote(doc, lanes=2)
        _mega_serve(storm, doc, writers, 24)
        st = mgr.docs[doc]
        return mgr, storm, st, mgr.map_entries(doc)

    mgr_t, storm_t, st_t, entries_t = play(trim=True)
    mgr_u, _storm_u, st_u, entries_u = play(trim=False)
    # Exactness: trimmed ≡ untrimmed converged map.
    assert entries_t == entries_u and entries_t
    # Bounded memory: the untrimmed twin holds one segment per combined
    # batch; the trimmed run holds a small suffix above the MSN floor.
    untrimmed = sum(len(log.lane_firsts) for log in st_u.logs)
    trimmed = sum(len(log.lane_firsts) for log in st_t.logs)
    assert untrimmed == 48  # 2 writers x 24 rounds
    assert trimmed <= 8, (trimmed, untrimmed)
    assert any(log.floor_lane > 0 for log in st_t.logs)
    # Recent catch-up (at/above the horizon) still serves...
    floor_doc = max(log.floor_doc for log in st_t.logs)
    recent = storm_t.records_overlapping(doc, floor_doc)
    assert recent
    # ...and below-horizon catch-up fails LOUDLY with the documented
    # reload-from-snapshot contract, never a silent gap.
    with pytest.raises(ValueError, match="reload from a snapshot"):
        storm_t.records_overlapping(doc, 0)


def test_re_promotion_epochs_match_never_promoted_twin(tmp_path):
    """ISSUE 13 satellite: a demoted doc RE-promotes into a fresh lane
    EPOCH (``::~mg1.<i>`` ids) — previously refused — and the full
    two-cycle lifecycle converges byte-identical to a never-promoted
    twin on entries, history and the sequencer checkpoint; a recovered
    stack replays BOTH cycles identically."""
    doc = "mega-epochs"

    def digest(svc, storm, seq, mh):
        cp = dataclasses.asdict(seq.checkpoint(doc))
        cp.pop("log_offset", None)
        for c in cp["clients"]:
            c["last_update"] = 0
        return {
            "map": mh.map_entries(doc, storm.datastore, storm.channel),
            "history": [[m.sequence_number, m.client_sequence_number,
                         m.client_id]
                        for m in svc.get_deltas(doc, 0)],
            "sequencer": cp,
        }

    def play(root, promote):
        svc, storm, seq, mh, mgr = build_stack(root, lanes=2)
        writers = [svc.connect(doc, lambda m: None).client_id
                   for _ in range(2)]
        svc.pump()
        storm.checkpoint()  # genesis: the recovery restore source
        if promote:
            mgr.promote(doc, lanes=2)
            assert mgr.docs[doc].epoch == 0
        _mega_serve(storm, doc, writers, 2, r0=0)
        if promote:
            mgr.demote(doc)
            mgr.promote(doc, lanes=2)  # the re-promotion under test
            assert mgr.docs[doc].epoch == 1
            assert all("::~mg1." in lid for lid in mgr.lane_ids(doc))
        _mega_serve(storm, doc, writers, 2, r0=2)
        if promote:
            mgr.demote(doc)
        storm.flush()
        return svc, storm, seq, mh, digest(svc, storm, seq, mh)

    root = str(tmp_path / "cycles")
    *_stack, cycled = play(root, promote=True)
    *_twin, plain = play(str(tmp_path / "twin"), promote=False)
    assert cycled == plain
    # Recovery replays both promotion cycles from the WAL controls.
    svc2, storm2, seq2, mh2, mgr2 = build_stack(root, lanes=2)
    storm2.recover()
    assert mgr2.has_history(doc) and not mgr2.is_promoted(doc)
    assert mgr2.docs[doc].epoch == 1
    assert mgr2.past_epochs[doc][0].epoch == 0
    assert digest(svc2, storm2, seq2, mh2) == cycled


def test_join_mid_promotion_matches_single_lane_twin(tmp_path):
    """Round-17 satellite (ROADMAP item 3 residue): a CLIENT_JOIN that
    lands WHILE the doc is promoted now sequences at the doc's TRUE
    head — routerlicious routes membership through the mirror, which
    fast-forwards the frozen doc row, lets the join take mirror.seq+1
    through the normal deli path, and journals a ``member`` control.
    The full lifecycle (promote → serve → join → post-join writes →
    demote) must converge byte-identical to a never-promoted twin, and
    a recovered stack must replay the membership control identically.
    Before the interception the join was adopt-without-sequence: its
    stale doc-row seq collided with the lane-combined stream and the
    twin's histories diverged."""
    doc = "mega-join"

    def digest(svc, storm, seq, mh):
        cp = dataclasses.asdict(seq.checkpoint(doc))
        cp.pop("log_offset", None)
        for c in cp["clients"]:
            c["last_update"] = 0
        return {
            "map": mh.map_entries(doc, storm.datastore, storm.channel),
            "history": [[m.sequence_number, m.client_sequence_number,
                         int(m.type), m.client_id]
                        for m in svc.get_deltas(doc, 0)],
            "sequencer": cp,
        }

    def serve(storm, participants, r0, rounds):
        # participants: (client, base_round) — cseqs restart per client.
        for r in range(r0, r0 + rounds):
            for w, (client, base) in enumerate(participants):
                storm.submit_frame(None, {
                    "rid": f"{r}.{w}",
                    "docs": [[doc, client, 1 + (r - base) * K, -1, K]]},
                    memoryview(storm_words(21, r, w).tobytes()))
            storm.flush()

    def play(root, promote):
        svc, storm, seq, mh, mgr = build_stack(root, lanes=2)
        writers = [svc.connect(doc, lambda m: None).client_id
                   for _ in range(2)]
        svc.pump()
        storm.checkpoint()
        if promote:
            mgr.promote(doc, lanes=2)
        serve(storm, [(w, 0) for w in writers], 0, 2)
        # THE mid-promotion join: a third client connects while the
        # doc is sharded (the twin connects at the same point).
        late = svc.connect(doc, lambda m: None).client_id
        svc.pump()
        if promote:
            # Sequenced, not just adopted: the mirror's head advanced
            # by exactly the join op.
            st = mgr.docs[doc]
            assert late in st.mirror.writers
        serve(storm, [(w, 0) for w in writers] + [(late, 2)], 2, 2)
        if promote:
            mgr.demote(doc)
        storm.flush()
        return svc, storm, seq, mh, digest(svc, storm, seq, mh)

    root = str(tmp_path / "sharded")
    *_s, sharded = play(root, promote=True)
    *_t, plain = play(str(tmp_path / "twin"), promote=False)
    assert sharded == plain
    # The join is IN the doc history exactly once, at the same seq.
    from fluidframework_tpu.protocol.messages import MessageType
    joins = [h for h in sharded["history"]
             if h[2] == int(MessageType.CLIENT_JOIN)]
    assert joins == [h for h in plain["history"]
                     if h[2] == int(MessageType.CLIENT_JOIN)]
    assert len(joins) == 3
    # Recovery replays the membership control at the identical point.
    svc2, storm2, seq2, mh2, mgr2 = build_stack(root, lanes=2)
    storm2.recover()
    assert digest(svc2, storm2, seq2, mh2) == sharded


def test_idle_eject_inside_round_defers_membership(tmp_path):
    """Round-18 satellite (the promotion-window seam's last gap): a
    membership op firing INSIDE a storm round (the idle-eject cadence
    runs off the round's pump) no longer falls back to legacy
    adopt-at-decide — it parks on the deferred queue and the flush
    maintenance cadence orders it through the FULL mirror path, so the
    leave sequences at the doc's true head exactly like a top-level
    membership op."""
    from fluidframework_tpu.protocol.messages import MessageType
    from fluidframework_tpu.server.sequencer import RawOperation

    doc = "mega-defer"
    svc, storm, seq, mh, mgr = build_stack(str(tmp_path), lanes=2)
    writers = [svc.connect(doc, lambda m: None).client_id
               for _ in range(2)]
    svc.pump()
    storm.checkpoint()
    mgr.promote(doc, lanes=2)
    for r in range(2):
        for w, client in enumerate(writers):
            storm.submit_frame(None, {
                "rid": f"{r}.{w}",
                "docs": [[doc, client, 1 + r * K, -1, K]]},
                memoryview(storm_words(21, r, w).tobytes()))
        storm.flush()
    leave = RawOperation(client_id=None, type=MessageType.CLIENT_LEAVE,
                         data=writers[1], timestamp=5)
    # Simulate the idle-eject path firing mid-round: the intercept must
    # DEFER (never order, never legacy-adopt).
    storm._in_round = True
    try:
        svc._order_membership(doc, leave)
    finally:
        storm._in_round = False
    assert len(mgr._deferred_members) == 1
    assert mgr.docs[doc].mirror.writers[writers[1]].active  # not yet
    # The next flush's maintenance cadence drains it through the full
    # mirror path: settled, sequenced at the true head, journaled.
    storm.flush()
    assert not mgr._deferred_members
    assert not mgr.docs[doc].mirror.writers[writers[1]].active
    mirror_seq = mgr.docs[doc].mirror.seq
    leaves = [m for m in svc.get_deltas(doc, 0)
              if m.type == MessageType.CLIENT_LEAVE]
    assert [m.sequence_number for m in leaves] == [mirror_seq]
    # Post-leave serving + demotion stay exact, and recovery replays
    # the deferred-then-ordered member control identically.
    storm.submit_frame(None, {
        "rid": "post", "docs": [[doc, writers[0], 1 + 2 * K, -1, K]]},
        memoryview(storm_words(21, 2, 0).tobytes()))
    storm.flush()
    mgr.demote(doc)
    storm.flush()
    live = mh.map_entries(doc, storm.datastore, storm.channel)
    storm._group_wal.close()
    svc2, storm2, seq2, mh2, mgr2 = build_stack(str(tmp_path), lanes=2)
    storm2.recover()
    assert mh2.map_entries(doc, storm2.datastore,
                           storm2.channel) == live
    storm2._group_wal.close()
