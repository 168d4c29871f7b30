"""CPU rehearsal of the per-op matrix cell (matrix_grid_1k.flood) at a
tiny size: the driver end to end through the bridge, the control, planted
faults, the per-op stage ledger's readers, and the handle-indexed tick's
byte count."""

import time

import jax
import pytest

from benchmark import harness, matrix_bytes

CELL = "matrix_grid_1k.flood"
TINY = {"docs": 2, "rows": 12, "cols": 12, "writers_per_doc": 8,
        "client_procs": 2, "client_slots": 32, "vec_slots": 256,
        "handles": 1024}
#: Structural ops at a share the tiny grid feels within seconds.
CHURN = {"mix": {"set_cell": 0.8, "insert_rows": 0.06, "remove_rows": 0.04,
                 "insert_cols": 0.06, "remove_cols": 0.04}}


def rehearse(seconds=2.0, trace=False, **kw):
    return harness.run_cell(CELL, 2**31 + 4242, seconds, trace,
                            time.perf_counter(), require_tpu=False,
                            sizes=TINY, traffic=CHURN,
                            log=lambda *a, **k: None, **kw)


@pytest.fixture(autouse=True)
def _fresh_programs():
    """A planted fault must be traced into the tick, not served from the
    in-memory jit cache of an earlier test (and must not leak out)."""
    jax.clear_caches()
    yield
    jax.clear_caches()


def test_matrix_cell_is_correct_and_control_is_not():
    result, checks = rehearse(control=True)
    assert result["correct"], checks
    assert result["control"]["correct"] is False, result["control"]
    assert set(result["metrics"]) == {"ops_per_s", "setup_s"}
    assert result["attempted"] > 0 and result["failed"] == 0
    assert result["window"]["compiles"] == 0
    assert result["window"]["ledger_ticks"] > 0


def test_traced_run_reports_the_perop_layers():
    result, _ = rehearse(seconds=3.0, trace=True)
    assert result["correct"]
    names = set(result["metrics"])
    assert {"perop_front_ms.matrix", "deli_ms.matrix",
            "merge_flush_ms.matrix", "broadcast_ms.matrix", "log_ms.matrix",
            "untraced_ms.matrix", "frame_wait_ms.matrix",
            "gc_ms.matrix"} <= names
    assert result["metrics"]["untraced_ms.matrix"]["value"] >= 0
    # No TPU plane on the CPU: no device number is printed.
    assert not any(n.startswith(("device_idle_share", "matrix_apply"))
                   for n in names)


def _plant(monkeypatch, wrap):
    from fluidframework_tpu.ops import matrix_kernel as mxk

    orig = mxk._write_cells
    monkeypatch.setattr(mxk, "_write_cells",
                        lambda val, seq, rh, ch, steps: wrap(
                            orig, val, seq, rh, ch, steps))


@pytest.mark.parametrize("fault", ["cells_dropped", "cols_for_rows"])
def test_planted_matrix_faults_fail_the_check(monkeypatch, fault):
    if fault == "cells_dropped":
        _plant(monkeypatch, lambda orig, val, seq, rh, ch, steps: (val, seq))
    else:
        _plant(monkeypatch, lambda orig, val, seq, rh, ch, steps: orig(
            val, seq, ch, rh, steps))
    result, checks = rehearse()
    assert result["correct"] is False
    assert checks["grid_bad_docs"]["value"] > 0


def test_matrix_tick_bytes_count_tables_ops_and_cells():
    # 2 axes x 8 docs x 2048 slots x (8 + 9) words, read and written;
    # one 1,024-cell tick of ops; each cell's value and seq written.
    got = matrix_bytes.matrix_tick_bytes(8, 2048, 257, 64, 1024)
    tables = 2 * 8 * 2048 * (8 + 9)
    ops = 8 * (1024 * 8 + 64 * 10)
    assert got == 4 * (2 * tables + ops + 2 * 8 * 1024)
