"""Kill-mid-tick chaos harness (tools/chaos.py): the serving process is
hard-killed (os._exit via utils/faults.py crashpoints) at the dangerous
points of the durability pipeline, restarted over the same directory,
and every recovered plane — sequenced history, map state, sequencer
checkpoints — must be byte-identical to an uninterrupted twin run, with
no durably-acked op ever lost.

Tier-1 runs a seeded smoke over one kill point per failure class
(volatile-state loss / torn group commit / torn checkpoint); the full
randomized kill-point × seed matrix is the `slow` soak.
"""

import json

import pytest

from fluidframework_tpu.tools import chaos
from fluidframework_tpu.utils import faults

_CFG = dict(seed=0, docs=2, k=8, ticks=5, cp_every=2)

#: (kill point, hit count chosen so the plan actually fires mid-run)
_SMOKE = [("storm.mid_tick", 3), ("wal.pre_fsync", 2),
          ("snapshot.pre_publish", 1)]


@pytest.fixture(scope="session")
def twin_digest(tmp_path_factory):
    """One uninterrupted twin run shared by every smoke scenario."""
    life = chaos._spawn_life(
        str(tmp_path_factory.mktemp("twin")), resume_from=None,
        kill_env=None, timeout=300, **_CFG)
    assert life["returncode"] == 0, life["stderr"]
    assert life["digest"] is not None
    return life["digest"]


@pytest.mark.parametrize("point,hits", _SMOKE,
                         ids=[p for p, _ in _SMOKE])
def test_chaos_smoke_recovers_byte_identical(point, hits, tmp_path,
                                             twin_digest):
    report = chaos.run_chaos(str(tmp_path), point, kill_hits=hits,
                             twin_digest=twin_digest, **_CFG)
    # The plan must actually have killed the process — a smoke that never
    # crashes proves nothing.
    assert report["killed"], report
    assert report["lives"] >= 2
    # run_chaos already asserted digest equality + acked-op retention;
    # double-check the acked rounds cover the whole workload by the end.
    assert report["acked_rounds"] == list(range(_CFG["ticks"]))


def test_twin_digest_covers_every_plane(twin_digest):
    """The comparison surface is meaningful: history, map and sequencer
    planes all present and non-trivial (guards against the diff silently
    comparing empty dicts)."""
    docs = twin_digest["docs"]
    assert len(docs) == _CFG["docs"]
    for planes in docs.values():
        ops = [h for h in planes["history"] if h[4] == 8]  # OPERATION
        assert len(ops) == _CFG["ticks"] * _CFG["k"]
        assert planes["map"]  # converged LWW entries
        assert planes["sequencer"]["clients"]
        assert planes["sequencer"]["sequence_number"] > 0
    # Digest must be canonically serializable (the twin diff is bytewise).
    json.dumps(twin_digest, sort_keys=True)


@pytest.mark.soak  # multi-minute: ~26 serving-process lives per seed
@pytest.mark.slow
@pytest.mark.parametrize("seed", [0, 1])
def test_chaos_full_matrix(seed, tmp_path):
    """Every kill point × two hit positions, per seed — the full
    randomized matrix (soak tier)."""
    reports = chaos.run_matrix(str(tmp_path), points=chaos.KILL_POINTS,
                               seeds=(seed,), hit_positions=(1, 2),
                               docs=2, k=8, ticks=6, cp_every=2)
    killed = [r for r in reports if r["killed"]]
    # Most plans fire; every report (killed or not) already passed the
    # twin diff inside run_chaos/run_matrix.
    assert len(killed) >= len(reports) // 2, \
        [(r["kill_point"], r["kill_hits"], r["killed"]) for r in reports]


def test_kill_exit_code_is_distinct():
    assert faults.KILL_EXIT_CODE == 137


# -- residency kill classes (ISSUE 9): tier-1 smoke + slow matrix --------------

#: Pool capped at 2 of 3 docs: every round's frame against the
#: round-robin cold doc forces an LRU eviction + a hydration, so the
#: residency crashpoints genuinely fire mid-transition.
_RES_CFG = dict(seed=0, docs=3, k=8, ticks=5, cp_every=2, residency=2)

_RES_SMOKE = [("residency.mid_hydrate", 2), ("residency.mid_evict", 1)]


@pytest.fixture(scope="session")
def residency_twin_digest(tmp_path_factory):
    """Uninterrupted twin of the capped-pool workload (shared)."""
    life = chaos._spawn_life(
        str(tmp_path_factory.mktemp("res_twin")), resume_from=None,
        kill_env=None, timeout=300, **_RES_CFG)
    assert life["returncode"] == 0, life["stderr"]
    assert life["digest"] is not None
    return life["digest"]


@pytest.mark.parametrize("point,hits", _RES_SMOKE,
                         ids=[p for p, _ in _RES_SMOKE])
def test_residency_chaos_smoke_recovers_byte_identical(
        point, hits, tmp_path, residency_twin_digest):
    """Kill mid-hydrate / mid-evict: recovery must reconverge
    byte-identically with the uninterrupted twin and lose zero
    acked-durable ops — whether each doc died hot, cold, or halfway
    through the transition (the acceptance bar of ISSUE 9)."""
    report = chaos.run_chaos(str(tmp_path), point, kill_hits=hits,
                             twin_digest=residency_twin_digest,
                             **_RES_CFG)
    assert report["killed"], report
    assert report["lives"] >= 2
    assert report["acked_rounds"] == list(range(_RES_CFG["ticks"]))


@pytest.mark.soak
@pytest.mark.slow
@pytest.mark.parametrize("seed", [0, 1])
def test_residency_chaos_full_matrix(seed, tmp_path):
    """Every residency kill point × two hit positions, per seed."""
    reports = chaos.run_matrix(
        str(tmp_path), points=chaos.RESIDENCY_KILL_POINTS, seeds=(seed,),
        hit_positions=(1, 2), docs=3, k=8, ticks=6, cp_every=2,
        residency=2)
    killed = [r for r in reports if r["killed"]]
    assert len(killed) >= len(reports) // 2, \
        [(r["kill_point"], r["kill_hits"], r["killed"]) for r in reports]


# -- overlap-window kill classes (ISSUE 11): tier-1 smoke + slow matrix --------

#: Deterministically-firing overlap points for the smoke (the
#: fsync-complete-before-readback point needs the writer thread to win a
#: race, so it rides the slow matrix with the >=half-killed tolerance).
_OVERLAP_SMOKE = [("storm.overlap_dispatch", 2),
                  ("storm.readback_pre_wal", 2)]


@pytest.mark.parametrize("point,hits", _OVERLAP_SMOKE,
                         ids=[p for p, _ in _OVERLAP_SMOKE])
def test_overlap_chaos_smoke_recovers_byte_identical(point, hits, tmp_path,
                                                     twin_digest):
    """Kill inside the dispatch/fsync overlap window of the PIPELINED
    serving tick (ISSUE 11): tick N+1 dispatched while tick N's group
    commit is in flight, or results read back before the durable record
    reached the writer. Recovery must replay the durable prefix
    byte-identically and lose zero acked-durable ops — and because the
    shared twin ran UNPIPELINED, digest equality also proves pipelined
    serving converges identically to barrier serving."""
    report = chaos.run_chaos(str(tmp_path), point, kill_hits=hits,
                             twin_digest=twin_digest, pipelined=True,
                             **_CFG)
    assert report["killed"], report
    assert report["lives"] >= 2
    assert report["acked_rounds"] == list(range(_CFG["ticks"]))


def test_pipelined_clean_run_matches_unpipelined_twin(tmp_path,
                                                      twin_digest):
    """No kill at all: a pipelined child run (acks lagging the durable
    watermark, overlapped fsync/dispatch) must produce the exact same
    digest planes as the unpipelined twin — the pipelining is a
    scheduling change, never a semantic one."""
    life = chaos._spawn_life(str(tmp_path), resume_from=None,
                             kill_env=None, timeout=300, pipelined=True,
                             **_CFG)
    assert life["returncode"] == 0, life["stderr"]
    assert json.dumps(life["digest"], sort_keys=True) \
        == json.dumps(twin_digest, sort_keys=True)
    assert sorted(life["acked"]) == list(range(_CFG["ticks"]))


@pytest.mark.soak
@pytest.mark.slow
@pytest.mark.parametrize("seed", [0, 1])
def test_overlap_chaos_full_matrix(seed, tmp_path):
    """Every overlap-window kill point × two hit positions, per seed,
    through the pipelined child."""
    reports = chaos.run_matrix(
        str(tmp_path), points=chaos.OVERLAP_KILL_POINTS, seeds=(seed,),
        hit_positions=(1, 2), docs=2, k=8, ticks=6, cp_every=2,
        pipelined=True)
    killed = [r for r in reports if r["killed"]]
    assert len(killed) >= len(reports) // 2, \
        [(r["kill_point"], r["kill_hits"], r["killed"]) for r in reports]


# -- overload fault classes (ISSUE 5): tier-1 smoke + slow matrix --------------


class TestOverloadSmoke:
    """One fast scenario per new fault class. Each run_* raises on any
    violated invariant; the asserts here double-check the report shape."""

    def test_throttle_under_storm(self, tmp_path):
        # Exact counts only: at 8 docs x 16 ops a tick is a few ms of
        # fixed Python cost, so a wall-clock ratio here measures the
        # machine's other load. The latency bar runs at the serving
        # shape in test_overload_full_matrix.
        report = chaos.run_overload(str(tmp_path), num_docs=8, k=16,
                                    rounds=6, p99_factor=None)
        assert report["shed_rate"] == 0.5  # exactly the 2x overflow shed
        assert report["acked_frames"] == report["shed_frames"] == 48

    def test_wal_fsync_failure(self, tmp_path):
        report = chaos.run_fsync_failure(str(tmp_path), num_docs=2, k=8,
                                         rounds=2)
        assert report["events"] == {"degraded_entered": True,
                                    "acks_withheld": True,
                                    "healed": True,
                                    "acks_after_heal": 2}
        assert report["breaker_opens"] >= 1

    def test_reconnect_storm_1k_clients(self):
        report = chaos.run_reconnect_storm(n_clients=1000)
        assert report["peak_attempts_per_s_after_wave"] \
            <= report["window_limit"]
        # Bounded recovery: within 1.5x the ideal drain of the herd.
        assert report["makespan_s"] <= 1.5 * report["ideal_drain_s"]

    def test_poison_doc_quarantine(self, tmp_path):
        report = chaos.run_poison_quarantine(str(tmp_path), num_docs=3,
                                             k=8, rounds=4)
        assert report["stats"] == {"quarantined_docs": 1,
                                   "readmitted_docs": 1}


@pytest.mark.soak
@pytest.mark.slow
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_overload_full_matrix(seed, tmp_path):
    """The slow tier: every overload fault class at larger shapes and
    multiple seeds (the kill-point matrix has its own soak above).
    The overload shape uses serving-sized ticks (128x128) so the latency
    ratio measures device work, not per-frame Python overhead — tiny
    ticks make the fixed shed cost look like a latency regression."""
    chaos.run_overload(str(tmp_path / "ov"), num_docs=128, k=128,
                       rounds=12, seed=seed)
    chaos.run_fsync_failure(str(tmp_path / "fs"), num_docs=8, k=32,
                            rounds=4, fail_times=5, seed=seed)
    chaos.run_poison_quarantine(str(tmp_path / "pq"), num_docs=8, k=32,
                                rounds=6, seed=seed)
    for n in (1000, 2000):
        chaos.run_reconnect_storm(n_clients=n, seed=seed)


_REBALANCE_CHILD = """
import sys
from fluidframework_tpu.dds.sequence import SharedString
from fluidframework_tpu.drivers.local_driver import LocalDocumentService
from fluidframework_tpu.runtime.container import Container
from fluidframework_tpu.server.durable_store import (
    DurableMessageBus, FileStateStore)
from fluidframework_tpu.server.merge_host import KernelMergeHost
from fluidframework_tpu.server.routerlicious import RouterliciousService
from fluidframework_tpu.utils import faults

# Small flush ticks + head-of-document inserts: once the table outgrows
# one 128-lane block (nb > 1), every tick lands in block 0 and the
# conditional rebalance fires. Bus AND store must be the durable pair
# (deli checkpoints reference bus offsets).
host = KernelMergeHost(flush_threshold=8)
service = RouterliciousService(bus=DurableMessageBus(sys.argv[1] + "/bus"),
                               store=FileStateStore(sys.argv[1] + "/state"),
                               merge_host=host)
c = Container.create_detached(LocalDocumentService(service, "doc"))
ds = c.runtime.create_datastore("default")
ds.create_channel("text", SharedString.channel_type)
c.attach()
# A second writer that never submits pins the MSN at its join, so the
# zamboni cannot coalesce the head-insert run and the table genuinely
# grows past one 128-lane block — the rebalance trigger shape.
idle = Container.load(LocalDocumentService(service, "doc"))
text = c.runtime.get_datastore("default").get_channel("text")
faults.arm()
for i in range(300):
    text.insert_text(0, f"edit{i} ")
print("SURVIVED", flush=True)  # the kill plan never fired
"""


# Same workload, but the kill plan arms pool.mid_retune: after the
# head-insert storm the host autotunes its block geometry (the explicit
# head_fraction pins the decision so the kill plan deterministically
# reaches a real re-block), and the process dies while the pool layout
# is moving wholesale.
_RETUNE_CHILD = _REBALANCE_CHILD.replace(
    'print("SURVIVED", flush=True)  # the kill plan never fired',
    'host.autotune_block_geometry(min_observations=1, '
    'fire_threshold=0.0, head_fraction=1.0)\n'
    'print("SURVIVED", flush=True)  # the kill plan never fired')


def _recover_host(tmp_path):
    """Merger-lambda replay of the scriptorium durable log into a FRESH
    host (the pool.mid_* recovery path)."""
    from fluidframework_tpu.drivers.local_driver import LocalDocumentService
    from fluidframework_tpu.runtime.container import Container
    from fluidframework_tpu.server.durable_store import (
        DurableMessageBus, FileStateStore)
    from fluidframework_tpu.server.merge_host import KernelMergeHost
    from fluidframework_tpu.server.routerlicious import RouterliciousService

    host = KernelMergeHost(flush_threshold=8)
    service = RouterliciousService(
        bus=DurableMessageBus(str(tmp_path / "bus")),
        store=FileStateStore(str(tmp_path / "state")),
        merge_host=host)
    service.connect("doc", lambda msgs: None)
    c = Container.load(LocalDocumentService(service, "doc"))
    text = c.runtime.get_datastore("default") \
        .get_channel("text").get_text()
    return host, c, text


def test_kill_mid_retune_replay_redecides_identically(tmp_path):
    """The pool.mid_retune kill class (round 11): the process dies while
    a geometry retune is moving the whole pool layout. Device state is
    volatile, so recovery = durable-log replay into a fresh host — and
    because the retune is a pure function of (state, block_slots), two
    independent replays that re-run the same retune must agree
    byte-for-byte on every pool plane (replay re-decides identically)."""
    import subprocess
    import sys as _sys

    import numpy as np

    env = dict(__import__("os").environ)
    env["FFTPU_CRASHPOINT"] = "pool.mid_retune:1"
    env.setdefault("JAX_PLATFORMS", "cpu")
    proc = subprocess.run(
        [_sys.executable, "-c", _RETUNE_CHILD, str(tmp_path)],
        capture_output=True, text=True, timeout=300, env=env)
    assert proc.returncode == faults.KILL_EXIT_CODE, (proc.returncode,
                                                      proc.stdout,
                                                      proc.stderr)

    host1, c1, text1 = _recover_host(tmp_path)
    host2, _c2, text2 = _recover_host(tmp_path)
    assert text1  # edits before the kill were durably sequenced
    assert text1 == text2
    assert host1.text("doc", "default", "text") == text1
    # Re-run the same retune on both replicas: the decision ladder and
    # the re-block are deterministic in the replayed state, so every
    # pool plane must stay byte-identical between the two recoveries.
    ret1 = host1.autotune_block_geometry(min_observations=1,
                                         fire_threshold=0.0,
                                         head_fraction=1.0)
    ret2 = host2.autotune_block_geometry(min_observations=1,
                                         fire_threshold=0.0,
                                         head_fraction=1.0)
    assert ret1 == ret2
    assert sorted(host1._merge_pools) == sorted(host2._merge_pools)
    for slots, p1 in host1._merge_pools.items():
        p2 = host2._merge_pools[slots]
        if hasattr(p1, "nb"):
            assert (p1.nb, p1.bk) == (p2.nb, p2.bk), slots
        for f in type(p1.state)._fields:
            assert np.array_equal(np.asarray(getattr(p1.state, f)),
                                  np.asarray(getattr(p2.state, f))), \
                (slots, f)
    # And the recovered, retuned host keeps sequencing.
    c1.runtime.get_datastore("default").get_channel("text") \
        .insert_text(0, "recovered ")
    assert host1.text("doc", "default", "text").startswith("recovered ")


def test_kill_mid_rebalance_recovers_from_durable_log(tmp_path):
    """The pool.mid_rebalance kill class (per-op merge path): the block
    pool's layout is mid-move when the process dies. The device state is
    volatile, so recovery = merger-lambda replay of the scriptorium
    durable log into a FRESH host — and the recovered device replica
    must match a scalar client replaying the same log."""
    import subprocess
    import sys as _sys

    env = dict(__import__("os").environ)
    env["FFTPU_CRASHPOINT"] = "pool.mid_rebalance:1"
    env.setdefault("JAX_PLATFORMS", "cpu")
    proc = subprocess.run(
        [_sys.executable, "-c", _REBALANCE_CHILD, str(tmp_path)],
        capture_output=True, text=True, timeout=300, env=env)
    assert proc.returncode == faults.KILL_EXIT_CODE, (proc.returncode,
                                                      proc.stdout,
                                                      proc.stderr)

    from fluidframework_tpu.drivers.local_driver import LocalDocumentService
    from fluidframework_tpu.runtime.container import Container
    from fluidframework_tpu.server.durable_store import (
        DurableMessageBus, FileStateStore)
    from fluidframework_tpu.server.merge_host import KernelMergeHost
    from fluidframework_tpu.server.routerlicious import RouterliciousService

    host = KernelMergeHost(flush_threshold=8)
    service = RouterliciousService(
        bus=DurableMessageBus(str(tmp_path / "bus")),
        store=FileStateStore(str(tmp_path / "state")),
        merge_host=host)
    # A reconnecting client instantiates the merger lambda, which replays
    # the durable op log into the fresh device host.
    service.connect("doc", lambda msgs: None)
    c = Container.load(LocalDocumentService(service, "doc"))
    client_text = c.runtime.get_datastore("default") \
        .get_channel("text").get_text()
    assert client_text  # edits before the kill were durably sequenced
    assert host.text("doc", "default", "text") == client_text
    # And the recovered service keeps sequencing.
    c.runtime.get_datastore("default").get_channel("text") \
        .insert_text(0, "recovered ")
    assert host.text("doc", "default", "text").startswith("recovered ")


# -- mega-doc kill classes (ISSUE 12): tier-1 smoke + slow matrix --------------

_MEGA_CFG = dict(docs=1, k=8, ticks=4, cp_every=2, megadoc=2, seed=0)

#: Deterministically-firing mega points for the smoke: the promotion
#: window (control journaled, lanes unseeded) and the combiner window
#: (doc seqs assigned, tick neither dispatched nor journaled). The
#: demotion point rides the slow matrix alongside.
_MEGA_SMOKE = [("megadoc.mid_promotion", 1), ("megadoc.mid_combine", 3)]


@pytest.fixture(scope="session")
def megadoc_twin_digest(tmp_path_factory):
    """Uninterrupted twin of the co-written mega-doc workload."""
    life = chaos._spawn_life(
        str(tmp_path_factory.mktemp("mega_twin")), resume_from=None,
        kill_env=None, timeout=300, **_MEGA_CFG)
    assert life["returncode"] == 0, life["stderr"]
    assert life["digest"] is not None
    return life["digest"]


@pytest.mark.parametrize("point,hits", _MEGA_SMOKE,
                         ids=[p for p, _ in _MEGA_SMOKE])
def test_megadoc_chaos_smoke_recovers_byte_identical(
        point, hits, tmp_path, megadoc_twin_digest):
    """Kill mid-promotion / mid-combiner-tick: recovery must replay the
    whole promoted lifecycle (control records re-promote at the same
    point, lane ticks re-combine in the same order) and reconverge
    byte-identically with zero acked-durable ops lost for EVERY writer
    (the ISSUE 12 acceptance bar)."""
    report = chaos.run_chaos(str(tmp_path), point, kill_hits=hits,
                             twin_digest=megadoc_twin_digest, **_MEGA_CFG)
    assert report["killed"], report
    assert report["lives"] >= 2
    assert report["acked_rounds"] == list(range(_MEGA_CFG["ticks"]))


def test_megadoc_demotion_chaos_recovers_byte_identical(
        tmp_path, megadoc_twin_digest):
    """Kill mid-demotion (control journaled, cross-lane fold not yet
    applied): recovery replays promote + every lane tick + the demote
    control and re-folds the identical doc row."""
    report = chaos.run_chaos(str(tmp_path), "megadoc.mid_demotion",
                             kill_hits=1, twin_digest=megadoc_twin_digest,
                             **_MEGA_CFG)
    assert report["killed"], report
    assert report["acked_rounds"] == list(range(_MEGA_CFG["ticks"]))


@pytest.mark.soak
@pytest.mark.slow
@pytest.mark.parametrize("seed", [0, 1])
def test_megadoc_chaos_full_matrix(seed, tmp_path):
    """Every mega kill point × two hit positions, per seed."""
    reports = chaos.run_matrix(
        str(tmp_path), points=chaos.MEGADOC_KILL_POINTS, seeds=(seed,),
        hit_positions=(1, 2), docs=1, k=8, ticks=5, cp_every=2,
        megadoc=2)
    killed = [r for r in reports if r["killed"]]
    assert len(killed) >= len(reports) // 2, \
        [(r["kill_point"], r["kill_hits"], r["killed"]) for r in reports]


# -- migration kill classes (ISSUE 13): tier-1 smoke + slow matrix -------------

_CLUSTER_CFG = dict(seed=0, docs=2, k=8, ticks=5, cp_every=2,
                    cluster=True, migrate_at=2)

#: Tier-1 smoke: the post-evict window (doc cold in the shared store,
#: NO host serving it, directory intent durable) — the nastiest phase.
#: The other two phases ride the slow matrix.
_MIGRATION_SMOKE = [("placement.post_evict", 1)]


@pytest.fixture(scope="session")
def cluster_twin_digest(tmp_path_factory):
    """The NEVER-MIGRATED twin cluster: digest equality against it is
    simultaneously the migrated ≡ never-migrated differential bar and
    the kill-recovery bar."""
    life = chaos._spawn_life(
        str(tmp_path_factory.mktemp("cluster_twin")), resume_from=None,
        kill_env=None, timeout=300,
        **dict(_CLUSTER_CFG, migrate_at=-1))
    assert life["returncode"] == 0, life["stderr"]
    assert life["digest"] is not None
    return life["digest"]


@pytest.mark.parametrize("point,hits", _MIGRATION_SMOKE,
                         ids=[p for p, _ in _MIGRATION_SMOKE])
def test_migration_chaos_smoke_recovers_byte_identical(
        point, hits, tmp_path, cluster_twin_digest):
    """Kill mid-migration: recovery rolls the durable intent FORWARD
    (the doc ends owned + served by the target) and the cluster
    reconverges byte-identical to a twin that never migrated, with
    zero acked-durable ops lost (the ISSUE 13 acceptance bar)."""
    report = chaos.run_chaos(str(tmp_path), point, kill_hits=hits,
                             twin_digest=cluster_twin_digest,
                             **_CLUSTER_CFG)
    assert report["killed"], report
    assert report["lives"] >= 2
    assert report["acked_rounds"] == list(range(_CLUSTER_CFG["ticks"]))


def test_cluster_clean_run_matches_never_migrated_twin(
        tmp_path, cluster_twin_digest):
    """No kill at all: the scripted live migration under writes alone
    must leave the cluster byte-identical to the never-migrated twin
    (migration is transparent to every compared plane)."""
    life = chaos._spawn_life(str(tmp_path), resume_from=None,
                             kill_env=None, timeout=300, **_CLUSTER_CFG)
    assert life["returncode"] == 0, life["stderr"]
    assert json.dumps(life["digest"], sort_keys=True) == json.dumps(
        cluster_twin_digest, sort_keys=True)
    assert life["acked"] == list(range(_CLUSTER_CFG["ticks"]))


@pytest.mark.soak
@pytest.mark.slow
@pytest.mark.parametrize("seed", [0, 1])
def test_migration_chaos_full_matrix(seed, tmp_path):
    """Slow soak: every migration phase × seed × hit position."""
    reports = chaos.run_matrix(
        str(tmp_path), points=chaos.MIGRATION_KILL_POINTS,
        seeds=(seed,), hit_positions=(1,),
        **{k: v for k, v in _CLUSTER_CFG.items() if k != "seed"})
    assert all(r["killed"] for r in reports)


# -- multi-tenant QoS kill classes (ISSUE 14): tier-1 smoke + slow matrix ------

#: Three tenants, the first at 10x (QOS_TENANTS/QOS_ABUSE_FACTOR in
#: chaos.py), composed through the deficit scheduler with a tick slot
#: budget — one workload round spans several budget-limited ticks, so
#: scheduler state genuinely moves between durable records.
_QOS_CFG = dict(seed=0, docs=2, k=8, ticks=4, cp_every=2)

_QOS_SMOKE = [("storm.qos_mid_compose", 2), ("wal.pre_fsync", 1)]


@pytest.fixture(scope="session")
def qos_twin_digest(tmp_path_factory):
    """Tenant-BLIND twin of the abusive-tenant workload (same frames,
    one tenant, no weights, no budget): equality with the fair arm
    proves fairness never changes converged replica state."""
    life = chaos._spawn_life(
        str(tmp_path_factory.mktemp("qos_twin")), resume_from=None,
        kill_env=None, timeout=300, qos="blind", **_QOS_CFG)
    assert life["returncode"] == 0, life["stderr"]
    assert life["digest"] is not None
    return life["digest"]


@pytest.mark.parametrize("point,hits", _QOS_SMOKE,
                         ids=[p for p, _ in _QOS_SMOKE])
def test_qos_chaos_smoke_recovers_byte_identical(
        point, hits, tmp_path, qos_twin_digest):
    """Kill mid-composition (scheduler charged, tick neither dispatched
    nor journaled) and pre-fsync under the 10x-abuser workload:
    recovery restores the deficit scheduler from the WAL headers, the
    resent frames recompose against it, and every plane reconverges
    byte-identical to the tenant-BLIND twin with zero acked-durable
    ops lost (the ISSUE 14 robustness bar)."""
    report = chaos.run_chaos(str(tmp_path), point, kill_hits=hits,
                             twin_digest=qos_twin_digest, qos=True,
                             **_QOS_CFG)
    assert report["killed"], report
    assert report["lives"] >= 2
    assert report["acked_rounds"] == list(range(_QOS_CFG["ticks"]))


def test_qos_fair_clean_run_matches_tenant_blind_twin(
        tmp_path, qos_twin_digest):
    """No kill at all: deficit-fair composition under a 10x abuser
    must leave every compared plane byte-identical to the tenant-blind
    FIFO twin — fairness moves latency, never bytes."""
    life = chaos._spawn_life(str(tmp_path), resume_from=None,
                             kill_env=None, timeout=300, qos="fair",
                             **_QOS_CFG)
    assert life["returncode"] == 0, life["stderr"]
    assert json.dumps(life["digest"], sort_keys=True) == json.dumps(
        qos_twin_digest, sort_keys=True)
    assert life["acked"] == list(range(_QOS_CFG["ticks"]))


@pytest.mark.soak
@pytest.mark.slow
@pytest.mark.parametrize("seed", [0, 1])
def test_qos_chaos_full_matrix(seed, tmp_path):
    """Slow soak: every QoS kill point × hit position, per seed."""
    reports = chaos.run_matrix(
        str(tmp_path), points=chaos.QOS_KILL_POINTS, seeds=(seed,),
        hit_positions=(1, 2), docs=2, k=8, ticks=5, cp_every=2,
        qos=True)
    killed = [r for r in reports if r["killed"]]
    assert len(killed) >= len(reports) // 2, \
        [(r["kill_point"], r["kill_hits"], r["killed"]) for r in reports]


# -- history-plane kill classes (ISSUE 15): tier-1 smoke + slow matrix ---------

#: Aggressive compaction cadence (summaries every ~2 rounds, retention
#: 1, trims under the checkpoint watermark) + a mid-run branch fork —
#: the mid-compaction/mid-fork windows genuinely fire.
_HIST_CFG = dict(seed=0, docs=2, k=8, ticks=6, cp_every=2)

_HIST_SMOKE = [("history.mid_compaction", 1), ("history.mid_fork", 1)]


@pytest.fixture(scope="session")
def history_twin_digest(tmp_path_factory):
    """NEVER-compacted twin (same frames, same fork, summarizer off):
    equality with the compacting arm proves summarization compaction +
    tail trim never change converged state."""
    life = chaos._spawn_life(
        str(tmp_path_factory.mktemp("hist_twin")), resume_from=None,
        kill_env=None, timeout=300, history="plain", **_HIST_CFG)
    assert life["returncode"] == 0, life["stderr"]
    assert life["digest"] is not None
    return life["digest"]


@pytest.mark.parametrize("point,hits", _HIST_SMOKE,
                         ids=[p for p, _ in _HIST_SMOKE])
def test_history_chaos_smoke_recovers_byte_identical(
        point, hits, tmp_path, history_twin_digest):
    """Kill mid-compaction (summary uploaded, head not flipped) and
    mid-fork (control journaled, branch not seeded): recovery must
    reconverge byte-identical to the never-compacted twin — converged
    maps, sequencer checkpoints, read_at-at-head, branch registry —
    with zero acked-durable ops lost (the ISSUE 15 chaos bar)."""
    report = chaos.run_chaos(str(tmp_path), point, kill_hits=hits,
                             twin_digest=history_twin_digest,
                             history=True, **_HIST_CFG)
    assert report["killed"], report
    assert report["lives"] >= 2
    assert report["acked_rounds"] == list(range(_HIST_CFG["ticks"]))


def test_history_compacting_clean_run_matches_plain_twin(
        tmp_path, history_twin_digest):
    """No kill at all: the compacting/trimming arm must digest
    byte-identical to the never-compacted twin — summaries move read
    cost and disk, never bytes."""
    life = chaos._spawn_life(str(tmp_path), resume_from=None,
                             kill_env=None, timeout=300,
                             history="compact", **_HIST_CFG)
    assert life["returncode"] == 0, life["stderr"]
    assert json.dumps(life["digest"], sort_keys=True) == json.dumps(
        history_twin_digest, sort_keys=True)
    assert life["acked"] == list(range(_HIST_CFG["ticks"]))


@pytest.mark.soak
@pytest.mark.slow
@pytest.mark.parametrize("seed", [0, 1])
def test_history_chaos_full_matrix(seed, tmp_path):
    """Slow soak: every history kill point × hit position, per seed."""
    reports = chaos.run_matrix(
        str(tmp_path), points=chaos.HISTORY_KILL_POINTS, seeds=(seed,),
        hit_positions=(1, 2), docs=2, k=8, ticks=6, cp_every=2,
        history=True)
    killed = [r for r in reports if r["killed"]]
    assert len(killed) >= len(reports) // 2, \
        [(r["kill_point"], r["kill_hits"], r["killed"]) for r in reports]


# -- replication kill classes (ISSUE 17): tier-1 smoke + slow matrix -----------

#: Leader + 2 followers under concurrent writes, a scripted mid-run
#: migration riding the same window; the resumed life ALWAYS promotes a
#: follower (the leader's directory is never reopened) — so digest
#: equality proves the replicated log + journaled heads alone carry the
#: whole acked state through a leader loss.
_REPL_CFG = dict(seed=0, docs=2, k=8, ticks=6, cp_every=2,
                 replication=True, migrate_at=3)

#: Tier-1 smoke: killed AFTER the batch shipped and quorum-acked but
#: before the leader's watermark settles — the op is acked-replicated,
#: so losing it would be the headline data-loss bug.
_REPL_SMOKE = [(chaos.REPLICATION_SMOKE_POINT, 2)]


@pytest.fixture(scope="session")
def replication_twin_digest(tmp_path_factory):
    """The never-killed, never-migrated replicated twin: equality
    against it is simultaneously the failover-recovery bar and the
    replication-is-transparent differential bar."""
    life = chaos._spawn_life(
        str(tmp_path_factory.mktemp("repl_twin")), resume_from=None,
        kill_env=None, timeout=300,
        **dict(_REPL_CFG, migrate_at=-1))
    assert life["returncode"] == 0, life["stderr"]
    assert life["digest"] is not None
    assert life["failovers"] == []  # nothing died in the twin
    return life["digest"]


@pytest.mark.parametrize("point,hits", _REPL_SMOKE,
                         ids=[p for p, _ in _REPL_SMOKE])
def test_replication_chaos_smoke_promotes_follower(
        point, hits, tmp_path, replication_twin_digest):
    """kill -9 the replicated leader mid-storm (concurrent writes, an
    in-flight migration): a follower promotes under the same label,
    the converged digest is byte-identical to the never-killed twin,
    zero acked-replicated ops are lost, and the failover blackout is
    bounded and reported (the ISSUE 17 acceptance bar)."""
    report = chaos.run_chaos(str(tmp_path), point, kill_hits=hits,
                             twin_digest=replication_twin_digest,
                             **_REPL_CFG)
    assert report["killed"], report
    assert report["lives"] >= 2
    assert report["acked_rounds"] == list(range(_REPL_CFG["ticks"]))
    blackouts = report["failover_blackouts_ms"]
    assert len(blackouts) == report["lives"] - 1  # one per promotion
    assert all(0 < b < 30_000 for b in blackouts), blackouts


@pytest.mark.soak
@pytest.mark.slow
@pytest.mark.parametrize("seed", [0, 1])
def test_replication_chaos_full_matrix(seed, tmp_path):
    """Slow soak: every replication kill class (either side of the
    ship, torn group commit, mid-tick) × hit position, per seed — with
    the failover blackout p99 bounded across the whole matrix."""
    reports = chaos.run_matrix(
        str(tmp_path), points=chaos.REPLICATION_CHAOS_POINTS,
        seeds=(seed,), hit_positions=(1, 2),
        **{k: v for k, v in _REPL_CFG.items() if k != "seed"})
    killed = [r for r in reports if r["killed"]]
    assert len(killed) >= len(reports) // 2, \
        [(r["kill_point"], r["kill_hits"], r["killed"]) for r in reports]
    blackouts = sorted(b for r in reports
                       for b in r["failover_blackouts_ms"])
    assert blackouts, "no promotion fired across the whole matrix"
    p99 = blackouts[min(len(blackouts) - 1,
                        int(0.99 * len(blackouts)))]
    assert p99 < 30_000, blackouts


# -- read-replica chaos (ISSUE 18: replica reads never change bytes) ----------

_REPLICAS_CFG = dict(seed=0, docs=2, k=8, ticks=6, cp_every=2,
                     replicas=True, migrate_at=3)

#: Tier-1 smoke: records applied/indexed on the replica but the tick's
#: viewer broadcast NOT yet published — the restarted replica (a fresh
#: from-zero re-poll of the durable follower WAL) must re-derive the
#: identical read surface.
_REPLICAS_SMOKE = [(chaos.REPLICAS_SMOKE_POINT, 2)]


@pytest.fixture(scope="session")
def replicas_twin_digest(tmp_path_factory):
    """The replica-LESS twin (same frames, every digest read served by
    the leader): equality against it is simultaneously the
    kill-recovery bar and the replica-reads-never-change-bytes bar."""
    life = chaos._spawn_life(
        str(tmp_path_factory.mktemp("replicas_twin")), resume_from=None,
        kill_env=None, timeout=300,
        **dict(_REPLICAS_CFG, replicas="off", migrate_at=-1))
    assert life["returncode"] == 0, life["stderr"]
    assert life["digest"] is not None
    return life["digest"]


@pytest.mark.parametrize("point,hits", _REPLICAS_SMOKE,
                         ids=[p for p, _ in _REPLICAS_SMOKE])
def test_replicas_chaos_smoke_rebuilds_read_surface(
        point, hits, tmp_path, replicas_twin_digest):
    """kill -9 the read replica mid-broadcast (viewers in the room,
    a directory-spread re-home mid-run): the restarted replica
    re-polls its durable follower WAL from zero, viewers re-home via
    the ordinary ``viewer_resync`` machinery, zero acked ops are lost,
    and every replica-served read digests byte-identical to the
    replica-less twin (the ISSUE 18 acceptance bar)."""
    report = chaos.run_chaos(str(tmp_path), point, kill_hits=hits,
                             twin_digest=replicas_twin_digest,
                             **_REPLICAS_CFG)
    assert report["killed"], report
    assert report["lives"] >= 2
    assert report["acked_rounds"] == list(range(_REPLICAS_CFG["ticks"]))


@pytest.mark.soak
@pytest.mark.slow
@pytest.mark.parametrize("seed", [0, 1])
def test_replicas_chaos_full_matrix(seed, tmp_path):
    """Slow soak: both replica kill classes (mid-apply between index
    and broadcast, mid-read inside a replica-served ``read_at``) × hit
    position, per seed."""
    reports = chaos.run_matrix(
        str(tmp_path), points=chaos.REPLICAS_CHAOS_POINTS,
        seeds=(seed,), hit_positions=(1, 2),
        **{k: v for k, v in _REPLICAS_CFG.items() if k != "seed"})
    killed = [r for r in reports if r["killed"]]
    assert len(killed) >= len(reports) // 2, \
        [(r["kill_point"], r["kill_hits"], r["killed"]) for r in reports]


# -- netsplit chaos (ISSUE 20: real sockets, fault-injected links) ------------


def test_netsplit_smoke_partition_parks_then_drains(tmp_path):
    """Tier-1 cut-the-cord smoke, F=1 over a REAL socket to a follower
    child process: a scripted full partition outlives the lease, the
    failure detector flips ``quorum_ok``, and the rounds written during
    the blackout PARK — no shed, no false ack. On heal the heartbeat
    resyncs the follower, the parked backlog drains, the delayed acks
    print, and the final state digests byte-identical to an in-process
    fault-free twin of the same seeded workload — with the incarnation
    fence proven on the wire at the end (the ISSUE 20 acceptance
    bar)."""
    report = chaos.run_netsplit(
        str(tmp_path), followers=1, seed=3, docs=2, k=4, ticks=6,
        cp_every=3, timeout=240.0, lease_s=0.4,
        script=chaos.netsplit_smoke_script(0.4))
    assert report["lives"] == 1 and not report["killed"]
    assert report["acked_rounds"] == list(range(6))
    # The blackout rounds were withheld at round end (parked), yet
    # every one of them is in acked_rounds above — parked, not lost.
    assert 1 in report["parked_rounds"], report
    assert report["zombie_fenced"] >= 1


@pytest.mark.soak
@pytest.mark.slow
@pytest.mark.parametrize("seed", [0, 1])
def test_netsplit_full_matrix_kill_leader_promotes_over_wire(
        seed, tmp_path):
    """Slow soak, F=2 follower child processes: the full scenario walk
    (follower partition with the quorum holding, leader cut from the
    whole quorum with writes parking, one-way ``partition_recv`` with
    real duplicate deliveries, a dup+reorder tail) and then a genuine
    ``kill -9`` of the leader at round 9. The resumed life promotes
    the most advanced follower OVER THE WIRE (graceful child shutdown
    releases its WAL), serves the remaining rounds, proves the dead
    incarnation is refused by the survivors, and the digest matches
    the fault-free twin with zero acked-round loss."""
    report = chaos.run_netsplit(
        str(tmp_path), followers=2, seed=seed, docs=2, k=8, ticks=12,
        cp_every=4, timeout=420.0, kill_at=9)
    assert report["killed"] and report["lives"] >= 2
    assert report["acked_rounds"] == list(range(12))
    # The scripted leader-from-quorum blackout parked its rounds.
    assert 4 in report["parked_rounds"], report
    blackouts = report["failover_blackouts_ms"]
    assert len(blackouts) == report["lives"] - 1
    assert all(0 < b < 30_000 for b in blackouts), blackouts
    assert report["zombie_fenced"] >= 2  # post-promotion + end-of-life
