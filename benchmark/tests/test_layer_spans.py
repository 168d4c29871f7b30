"""CPU rehearsal of the per-layer metrics that read the program's stage
spans: the traced flood cell reports its serving-thread account; the
4-chip cell, whose driver returns no ledger, reports none of them."""

import time
from pathlib import Path

import pytest

from benchmark import harness

ROOT = Path(__file__).resolve().parents[2]
TINY = {
    "map_storm_10k": {"docs": 128, "flush_threshold_docs": 128,
                      "sample": 16},
    "map_sharded_4chip": {"docs_per_chip": 128, "sample": 16},
}


def rehearse(workload, seconds):
    spec = harness.resolve(workload, ROOT)
    return harness.run_cell(workload, 2**31 + 4242, seconds, True,
                            time.perf_counter(), require_tpu=False,
                            root=ROOT, sizes=TINY[spec.cell["config"]],
                            log=lambda *a, **k: None)


def test_traced_flood_reports_the_serving_thread_account():
    result, checks = rehearse("map_storm_10k.flood", 3.0)
    assert result["correct"], checks
    got = result["metrics"]
    for name in ("frame_wait_ms.tput", "ack_send_ms.tput",
                 "wal_fsync_ms.tput", "untraced_ms.tput", "gc_ms.tput",
                 "service_pump_ms.tput"):
        assert name in got, name
        assert got[name]["value"] >= 0, name
    # The account closes to within the per-tick wall clock.
    wall = result["window"]["ledger_ms_per_tick"]["wall"]
    assert got["untraced_ms.tput"]["value"] < wall
    assert got["frame_wait_ms.tput"]["value"] > 0


def test_traced_sharded_reports_no_serving_thread_metric():
    result, checks = rehearse("map_sharded_4chip.flood", 2.0)
    assert result["correct"], checks
    got = result["metrics"]
    for name in ("frame_wait_ms.tput", "ack_send_ms.tput",
                 "wal_fsync_ms.tput", "untraced_ms.tput", "gc_ms.tput",
                 "service_pump_ms.tput"):
        assert name not in got, name


@pytest.mark.parametrize("name", ["frame_wait_ms", "ack_send_ms",
                                  "service_pump_ms", "untraced_ms", "gc_ms",
                                  "wal_fsync_ms"])
def test_readers_find_nothing_in_a_program_without_the_spans(name):
    """A run of a program whose ledger predates the spans (records
    without ``gc`` or the new stages, no WAL counters) reads as None."""
    from types import SimpleNamespace

    old = {"tick": 0, "queue_depth": 0, "batch_docs": 1, "batch_ops": 8,
           "wall": 5, "depth": 1, "scatter": 1, "wal_commit_wait": 2}
    run = SimpleNamespace(ledger=[old], host={}, counters={"ticks": 1},
                          trace=None)
    reader = harness.load_module(harness.reader_path("layer_metrics", name),
                                 f"t_{name}")
    assert reader.read(run) is None
