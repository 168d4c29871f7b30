"""Device-pool snapshot/restore (KernelMergeHost.export_state /
import_state): every device plane — block merge pools, map state, matrix
state — plus the host-side string/slot mappings round-trips through the
content-addressed snapshot store into a FRESH host that serves
identically, including scalar-routed channels and continued ingestion
after the restore."""

import random

import pytest

from fluidframework_tpu.drivers.local_driver import LocalDocumentService
from fluidframework_tpu.protocol.messages import (
    MessageType,
    SequencedDocumentMessage,
)
from fluidframework_tpu.runtime.container import Container
from fluidframework_tpu.server.durable_store import GitSnapshotStore
from fluidframework_tpu.server.local_server import LocalCollabServer
from fluidframework_tpu.server.merge_host import KernelMergeHost
from tests.test_matrix import get_matrix, make_matrix_doc
from tests.test_merge_host import get_parts, make_doc, random_edit


def seq_msg(seq, channel, contents, client="tail-client", ref=None,
            msn=None):
    return SequencedDocumentMessage(
        client_id=client, sequence_number=seq,
        minimum_sequence_number=msn if msn is not None else max(0, seq - 1),
        client_sequence_number=seq, reference_sequence_number=ref or seq - 1,
        type=MessageType.OPERATION,
        contents={"address": "default",
                  "contents": {"address": channel, "contents": contents}},
        timestamp=seq, data=None)


def build_host_with_traffic(max_client_slots=1024):
    """Text + map + matrix traffic through the real serving stack."""
    host = KernelMergeHost(flush_threshold=16,
                           max_client_slots=max_client_slots)
    server = LocalCollabServer(merge_host=host)
    rng = random.Random(5)
    c1 = make_doc(server, "doc0")
    c2 = Container.load(LocalDocumentService(server, "doc0"))
    for _ in range(12):
        for c in (c1, c2):
            text, root = get_parts(c)
            random_edit(rng, text)
            root.set(f"k{rng.randrange(6)}", rng.randrange(100))
    cm = make_matrix_doc(server, rows=3, cols=3)
    m = get_matrix(cm)
    for r in range(3):
        for col in range(3):
            m.set_cell(r, col, r * 3 + col)
    host.flush()
    return host


def docs_view(host):
    return {
        "text": host.text("doc0", "default", "text"),
        "rich": host.rich_text("doc0", "default", "text"),
        "map": host.map_entries("doc0", "default", "root"),
        "grid": host.matrix_grid("doc", "default", "grid"),
        "summary": host.summarize("doc0"),
    }


def roundtrip(host, tmp_path):
    """Export → the REAL snapshot store (chunked, content-addressed,
    wire-codec serialization) → import into a fresh host."""
    git = GitSnapshotStore(tmp_path / "git")
    handle = git.upload("__pools__", host.export_state())
    loaded = git.get("__pools__", handle)
    host2 = KernelMergeHost(flush_threshold=16,
                            max_client_slots=host.max_client_slots)
    host2.import_state(loaded)
    return host2


def test_export_import_reproduces_every_plane(tmp_path):
    host = build_host_with_traffic()
    host2 = roundtrip(host, tmp_path)
    assert docs_view(host2) == docs_view(host)


def test_restored_host_keeps_serving_identically(tmp_path):
    host = build_host_with_traffic()
    host2 = roundtrip(host, tmp_path)
    # The same sequenced tail into both hosts → identical convergence
    # (slot mappings, interning and seq frontiers all survived).
    base = host.summarize("doc0")["sequence_number"]
    tail = [
        seq_msg(base + 1, "text", {"type": "insert", "pos": 0,
                                   "text": "post-restore "}),
        seq_msg(base + 2, "root", {"type": "set", "key": "fresh",
                                   "value": 41}),
        seq_msg(base + 3, "text", {"type": "annotate", "start": 0,
                                   "end": 4, "props": {"b": True}}),
    ]
    for h in (host, host2):
        for m in tail:
            h.ingest("doc0", m)
        h.flush()
    assert docs_view(host2) == docs_view(host)
    assert host2.text("doc0", "default", "text").startswith("post-restore ")


def test_scalar_routed_channel_roundtrips(tmp_path):
    """A channel overflow-routed to the scalar engine exports its engine
    and keeps serving scalar-side after import."""
    host = KernelMergeHost(flush_threshold=8, max_client_slots=32)
    # More distinct writers than the client-slot ceiling routes the
    # channel off the device mid-stream.
    # msn pinned at 0: every writer stays in the collab window, so the
    # zamboni cannot coalesce the writer set back under the ceiling
    # (which would legitimately readmit the channel to the device).
    for seq in range(1, 41):
        host.ingest("sdoc", seq_msg(
            seq, "text", {"type": "insert", "pos": 0, "text": f"w{seq} "},
            client=f"writer-{seq}", msn=0))
    host.flush()
    assert host.stats["overflow_routed"] > 0
    key = [k for k in host._merge_rows if k.channel == "text"][0]
    assert host._merge_rows[key].scalar is not None

    git = GitSnapshotStore(tmp_path / "git")
    handle = git.upload("__pools__", host.export_state())
    host2 = KernelMergeHost(flush_threshold=8, max_client_slots=32)
    host2.import_state(git.get("__pools__", handle))
    assert host2._merge_rows[key].scalar is not None
    assert (host2.text("sdoc", "default", "text")
            == host.text("sdoc", "default", "text"))
    assert (host2.rich_text("sdoc", "default", "text")
            == host.rich_text("sdoc", "default", "text"))
    # Scalar serving continues identically after the restore.
    tail = seq_msg(41, "text", {"type": "remove", "start": 0, "end": 3},
                   client="writer-41", msn=0)
    for h in (host, host2):
        h.ingest("sdoc", tail)
        h.flush()
    assert (host2.text("sdoc", "default", "text")
            == host.text("sdoc", "default", "text"))


def test_import_requires_fresh_host(tmp_path):
    host = build_host_with_traffic()
    snap = host.export_state()
    with pytest.raises(AssertionError, match="fresh host"):
        host.import_state(snap)


def _assert_pools_identical(a, b):
    """Every merge pool byte-identical between two hosts: geometry AND
    all device planes (the replay-determinism comparison surface)."""
    import numpy as np
    assert sorted(a._merge_pools) == sorted(b._merge_pools)
    for slots, pa in a._merge_pools.items():
        pb = b._merge_pools[slots]
        assert type(pa) is type(pb), slots
        if hasattr(pa, "nb"):
            assert (pa.nb, pa.bk) == (pb.nb, pb.bk), slots
        for f in type(pa.state)._fields:
            assert np.array_equal(np.asarray(getattr(pa.state, f)),
                                  np.asarray(getattr(pb.state, f))), \
                (slots, f)


def test_geometry_retune_snapshot_replay_determinism(tmp_path):
    """The round-11 replay/restore determinism bar: a geometry retune +
    incremental-rebalance sequence survives export_state/import_state
    byte-identically, a restore of the PRE-retune snapshot that re-runs
    the same retune re-decides the same layout byte-for-byte, and the
    same sequenced tail (the WAL-tail replay analog) converges all
    replicas identically on the retuned geometry."""
    import random as _random

    from fluidframework_tpu.server.local_server import LocalCollabServer

    host = KernelMergeHost(flush_threshold=8)
    server = LocalCollabServer(merge_host=host)
    c = make_doc(server, "doc0")
    # A second writer that never submits pins the MSN, so the zamboni
    # cannot coalesce the head-insert run — the table genuinely grows
    # and the head-concentrated stream arms the rebalance trigger.
    Container.load(LocalDocumentService(server, "doc0"))
    text, _root = get_parts(c)
    for i in range(300):
        text.insert_text(0, f"edit{i} ")
    host.flush()
    assert host.stats["rebalances"] > 0  # incremental ladder exercised
    git = GitSnapshotStore(tmp_path / "git")
    pre = git.upload("__pools__", host.export_state())
    # The stream IS head-concentrated (every insert at pos 0); pin the
    # concentration estimate so every block pool — including the one the
    # doc migrated into — re-blocks, and the decision is deterministic
    # for the pre-retune-restore replica below.
    retuned = host.autotune_block_geometry(min_observations=1,
                                           fire_threshold=0.0,
                                           head_fraction=1.0)
    assert retuned, "head-concentrated stream never tripped the autotune"
    assert host.stats["geometry_retunes"] >= 1

    # (a) Replay re-decides identically: restore the PRE-retune snapshot
    # and apply the same retune decisions — byte-identical pools
    # (pool.retune is a pure function of (state, block_slots)).
    host2 = KernelMergeHost(flush_threshold=8)
    host2.import_state(git.get("__pools__", pre))
    for slots, (_nb, bk) in retuned.items():
        host2._merge_pools[slots].retune(bk)
    _assert_pools_identical(host, host2)

    # (b) The retuned geometry itself survives the snapshot seam (the
    # "block_geometry" stamp re-blocks the fresh pool before planes
    # load).
    post = git.upload("__pools__", host.export_state())
    host3 = KernelMergeHost(flush_threshold=8)
    host3.import_state(git.get("__pools__", post))
    for slots, (nb, bk) in retuned.items():
        p = host3._merge_pools[slots]
        assert (p.nb, p.bk) == (nb, bk), slots
    _assert_pools_identical(host, host3)

    # (c) The same sequenced tail through original and both restores
    # converges byte-identically — the tail keeps hammering the head so
    # the incremental rebalance re-fires on the retuned geometry.
    base = host.summarize("doc0")["sequence_number"]
    rng = _random.Random(11)
    tail = [seq_msg(base + 1 + i, "text",
                    {"type": "insert", "pos": 0,
                     "text": f"t{rng.randrange(100)} "})
            for i in range(24)]
    for h in (host, host2, host3):
        for m in tail:
            h.ingest("doc0", m)
        h.flush()
    assert (host2.text("doc0", "default", "text")
            == host3.text("doc0", "default", "text")
            == host.text("doc0", "default", "text"))
    _assert_pools_identical(host, host2)
    _assert_pools_identical(host, host3)


def test_tree_channels_are_flagged_for_log_replay(tmp_path):
    """Tree channels are not snapshotted (they rebuild from the durable
    op-log replay); export records their keys so callers know."""
    from fluidframework_tpu.dds.tree_core import ROOT_ID
    from tests.test_tree_host import get_tree, make_tree_doc, node

    host = KernelMergeHost(flush_threshold=4)
    server = LocalCollabServer(merge_host=host)
    c = make_tree_doc(server, "tdoc")
    get_tree(c).insert_node(
        node("n1"), {"referenceTrait": {"parent": ROOT_ID,
                                        "label": "children"},
                     "side": "end"})
    host.flush()
    snap = host.export_state()
    assert ["tdoc", "default", "tree"] in snap["tree_keys"]


def test_cell_log_snapshot_restores_into_cell_planes(tmp_path):
    """A snapshot of the matrix cell-log layout (cells as an append log
    of (row handle, col handle, value, seq) entries, written by the host
    before the handle-indexed planes) restores into the planes: every
    grid reads as the exporting host served it, the restored host keeps
    serving the same sequenced tail to the same grids, and its own
    export round-trips."""
    import json
    from pathlib import Path

    from fluidframework_tpu.protocol.codec import from_wire

    golden = json.loads((Path(__file__).parent / "goldens_snapshot"
                         / "matrix-cell-log.json").read_text())
    assert "cell_slots" in golden["snapshot"]["matrix"]
    host = KernelMergeHost(flush_threshold=16, row_capacity=2)
    host.import_state(golden["snapshot"])
    for doc, grid in golden["grids"].items():
        assert host.matrix_grid(doc, "default", "grid") == grid
    for doc, tail in golden["tail"].items():
        for m in tail:
            host.ingest(doc, from_wire(m))
    host.flush()
    for doc, grid in golden["grids_after_tail"].items():
        assert host.matrix_grid(doc, "default", "grid") == grid
    assert host.metrics.snapshot().get("matrix.ops_scalar", 0) == 0
    host2 = roundtrip(host, tmp_path)
    for doc, grid in golden["grids_after_tail"].items():
        assert host2.matrix_grid(doc, "default", "grid") == grid
