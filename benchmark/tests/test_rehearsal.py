"""CPU rehearsal of the benchmark: every driver end to end at a tiny size
(the program's kernels on their CPU path), the command's refusal without a
TPU, and a data-only cell that runs with no edit to any existing file."""

import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

import pytest

from benchmark import harness

ROOT = Path(__file__).resolve().parents[2]

#: Tiny sizes of each configuration (the CPU cannot hold the real ones).
TINY = {
    "map_storm_10k": {"docs": 128, "flush_threshold_docs": 128,
                      "sample": 16},
    "map_sharded_4chip": {"docs_per_chip": 128, "sample": 16},
}


def rehearse(workload, seconds=2.0, trace=False, root=ROOT, **kw):
    spec = harness.resolve(workload, root)
    return harness.run_cell(workload, 2**31 + 12345, seconds, trace,
                            time.perf_counter(), require_tpu=False,
                            root=root, sizes=TINY[spec.cell["config"]],
                            log=lambda *a, **k: None, **kw)


CELLS = [c["name"] for c in json.loads(
    (ROOT / "BENCHMARK.json").read_text())["workloads"]]


@pytest.mark.parametrize("workload", CELLS)
def test_cell_runs_and_is_correct(workload):
    result, checks = rehearse(workload)
    assert result["correct"], checks
    spec = harness.resolve(workload)
    assert set(result["metrics"]) == {m["name"] for m in spec.e2e}
    assert result["device"]["platform"] == "cpu"
    assert result["attempted"] > 0 and result["failed"] == 0


@pytest.mark.parametrize("workload", CELLS)
def test_control_fails_the_check(workload):
    """The control's outputs in the program's place, through the same
    check and comparison that decide ``correct``."""
    result, checks = rehearse(workload, control=True)
    assert result["correct"], checks
    assert result["control"]["correct"] is False, result["control"]


def test_traced_run_reports_host_layers():
    result, _ = rehearse("map_storm_10k.flood", seconds=3.0, trace=True)
    assert result["correct"]
    names = set(result["metrics"])
    assert {"front_ms.tput", "wal_ms.tput", "flood_ack_ms_p95.tput"} <= names
    # No TPU plane on the CPU: no device number is printed.
    assert not any(n.startswith("device_idle_share") for n in names)
    assert "window_s" in result["device"]


def test_run_exits_nonzero_without_a_tpu():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "map_storm_10k.flood", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True,
        text=True, timeout=120)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr


def test_data_only_cell_runs(tmp_path):
    """A new cell from a traffic file and a workloads entry alone (an open
    loop, which no committed cell runs): the checkout's existing files
    are copied unchanged."""
    shutil.copytree(ROOT / "benchmark" / "configs",
                    tmp_path / "benchmark" / "configs")
    shutil.copytree(ROOT / "benchmark" / "traffic",
                    tmp_path / "benchmark" / "traffic")
    fixture = Path(__file__).parent / "fixtures" / "storm_paced_slow.json"
    shutil.copy(fixture, tmp_path / "benchmark" / "traffic")
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["workloads"].append({
        "name": "map_storm_10k.paced_slow", "config": "map_storm_10k",
        "traffic": "storm_paced_slow", "chips": 1,
        "why": "fixture: a slower open loop, added as data only"})
    for metric in bench["end_to_end"]:
        if "workloads" in metric:
            metric["workloads"].append("map_storm_10k.paced_slow")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    result, checks = rehearse("map_storm_10k.paced_slow", root=tmp_path)
    assert result["correct"], checks
    assert {"ops_per_s", "setup_s"} == set(result["metrics"])
    assert result["window"]["thirds"]["ack_ms_p50"][0] > 0
