"""The benchmark harness: finds a cell's configuration, traffic, driver and
metric readers by the names in ``BENCHMARK.json``, runs one measured
window, checks the outputs and builds the result line.

Nothing here knows a cell, a configuration or a metric by name: a later
PR adds a cell by adding data files and entries, a metric by adding a
reader under ``e2e_metrics/`` or ``layer_metrics/``, and an entry point by
adding a driver under ``drivers/``.

Driver contract (``drivers/<entry>.py`` defines ``Driver``)::

    d = Driver(config, traffic, seed, devices, scratch)
    d.setup()                  # data from the seed, clients, warm-up
    d.window(seconds, trace)   # the measured window; trace may be None
    d.collect()                # read the program's outputs, free it
    d.data()                   # RunData fields for the metric readers
    d.check()                  # {number compared: value}
    d.use_control()            # put the control's outputs in the
                               # program's place; check() then reads them
    d.close()                  # stop every process it started

``d.data()`` may carry ``rate_thirds``: the rate (or, for an open loop,
the median ack in ms) over the first, middle and last third of the
window, which shows whether the window was steady.

A driver with a trace calls ``trace.start()`` and ``trace.stop()`` around
a few steady ticks inside its window.
"""

from __future__ import annotations

import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path
from types import SimpleNamespace

from . import trace_reduce

#: Fields of a stage-ledger record that are not times.
_LEDGER_IDS = ("tick", "queue_depth", "batch_docs", "batch_ops", "depth")

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
CACHE_DIR = ROOT / ".jax_cache"


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def percentile(values, q: float) -> float:
    """Nearest-rank percentile (index ceil(q*n) - 1) of ``values``."""
    import math

    s = sorted(values)
    if not s:
        raise ValueError("percentile of no values")
    return s[min(len(s) - 1, max(0, math.ceil(q * len(s)) - 1))]


def ledger_ms(run, stages) -> float | None:
    """Mean ms per tick of the named storm ledger stages over the window's
    ticks (None where the cell keeps no ledger)."""
    if not run.ledger:
        return None
    ns = sum(rec[s] for rec in run.ledger for s in stages)
    return ns / len(run.ledger) / 1e6


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def resolve(workload: str, root: Path = ROOT) -> SimpleNamespace:
    """The cell, its configuration and traffic files, and its metrics, as
    ``BENCHMARK.json`` under ``root`` names them."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {c["name"]: c for c in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config = json.loads((root / conf["file"]).read_text())
    traffic = json.loads(
        (root / "benchmark" / "traffic" / f"{cell['traffic']}.json")
        .read_text())
    e2e = [m for m in bench["end_to_end"]
           if workload in m.get("workloads", [workload])]
    reported = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if (workload in m["workloads"] if "workloads" in m
                 else m["moves"] in reported)]
    return SimpleNamespace(cell=cell, config=config, traffic=traffic,
                           e2e=e2e, layer=layer)


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout, unless the environment names one. Call before JAX starts."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CACHE_DIR)
        os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    os.makedirs(path, exist_ok=True)
    import jax

    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.1)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def chips(n: int, require_tpu: bool = True) -> list:
    import jax

    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {devices[0].platform})")
    if len(devices) < n:
        raise NoChip(f"the cell asks for {n} chips, JAX found "
                     f"{len(devices)}")
    return devices[:n]


class Trace:
    """The profiler around a few steady ticks (host Python tracer off, so
    the buffers hold the ticks and nothing is dropped)."""

    def __init__(self, scratch: str) -> None:
        self.dir = os.path.join(scratch, "trace")
        self._ann = None
        self._stopped = False

    def start(self) -> None:
        import jax.profiler as jp

        if self._ann is not None or self._stopped:
            return
        opts = jp.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jp.start_trace(self.dir, profiler_options=opts)
        self._ann = jp.TraceAnnotation(trace_reduce.WINDOW_SPAN)
        self._ann.__enter__()

    def stop(self) -> None:
        import jax.profiler as jp

        if self._ann is None:
            return
        self._stopped = True
        self._ann.__exit__(None, None, None)
        self._ann = None
        jp.stop_trace()

    @property
    def started(self) -> bool:
        return self._ann is not None

    def summary(self, kernels: dict[str, str]) -> dict | None:
        path = trace_reduce.find_xplane(self.dir)
        if path is None:
            return None
        return trace_reduce.reduce(trace_reduce.load(path), kernels)


class _CompileCount:
    """Counts the programs JAX sends to its compiler (or loads from the
    persistent cache) while open: the window should send none."""

    _active: list = []

    def __init__(self) -> None:
        self.n = 0

    def __enter__(self):
        import jax

        if not _CompileCount._active:
            jax.monitoring.register_event_listener(_CompileCount._event)
        _CompileCount._active.append(self)
        return self

    def __exit__(self, *exc) -> None:
        _CompileCount._active.remove(self)

    @staticmethod
    def _event(event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            for counter in _CompileCount._active:
                counter.n += 1


def peaks_for(kind: str) -> dict:
    table = json.loads((BENCH_DIR / "peaks.json").read_text())["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    return table[kind]


def memory_peak(devices) -> int:
    peaks = []
    for dev in devices:
        stats = dev.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0


def reader_path(folder: str, name: str) -> Path:
    """A metric's reader: ``<name>.py``, else the reader of its base name
    (``wal_ms.py`` reads both ``wal_ms.tput`` and ``wal_ms.lat``)."""
    path = BENCH_DIR / folder / f"{name}.py"
    if not path.exists():
        path = BENCH_DIR / folder / f"{name.split('.')[0]}.py"
    return path


def read_metrics(specs: list[dict], run: SimpleNamespace,
                 folder: str) -> dict:
    """Each spec's reader, found by name; a reader that finds nothing to
    read returns None and the metric is left out."""
    out = {}
    for spec in specs:
        reader = load_module(reader_path(folder, spec["name"]),
                             f"bench_metric_{spec['name']}")
        value = reader.read(run)
        if value is not None:
            out[spec["name"]] = {"value": float(value), "unit": spec["unit"]}
    return out


def judge(limits: dict, readings: dict) -> tuple[bool, dict]:
    """``correct`` and each number compared beside its limit: a number
    that is missing, or above its limit, makes the run not correct."""
    checks = {name: {"value": readings.get(name), "limit": limit}
              for name, limit in limits.items()}
    correct = all(c["value"] is not None and c["value"] <= c["limit"]
                  for c in checks.values())
    return correct, checks


class _WindowCost:
    """What the process paid inside the window besides serving: Python's
    garbage collections (count, seconds; the full ones apart, with the
    longest and when it began)."""

    def __enter__(self):
        import gc

        self.gc_n, self.gc_s, self._t = 0, 0.0, None
        self.full_n, self.full_s, self.full_max = 0, 0.0, (0.0, None)
        self._t0 = time.perf_counter()
        gc.callbacks.append(self._on_gc)
        return self

    def _on_gc(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            took = time.perf_counter() - self._t
            self.gc_n += 1
            self.gc_s += took
            if info.get("generation") == 2:
                self.full_n += 1
                self.full_s += took
                if took > self.full_max[0]:
                    self.full_max = (took, self._t - self._t0)

    def __exit__(self, *exc) -> None:
        import gc

        gc.callbacks.remove(self._on_gc)


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             t_start: float, require_tpu: bool = True,
             root: Path = ROOT, sizes: dict | None = None,
             traffic: dict | None = None, control: bool = False,
             log=print) -> tuple[dict, dict]:
    """One run of a cell. Returns (result line, checks). ``sizes``
    overrides the configuration's sizes (the CPU rehearsal only),
    ``traffic`` the mix's parameters (the knee sweep only). With
    ``control``, the control's outputs then take the program's place and
    go through the same check: the result gains "control" with its
    ``correct`` and numbers compared."""
    spec = resolve(workload, root)
    config = dict(spec.config)
    if sizes:
        config["sizes"] = {**config["sizes"], **sizes}
    mix = {**spec.traffic, **(traffic or {})}
    devices = chips(spec.cell["chips"], require_tpu)
    driver_mod = load_module(
        BENCH_DIR / "drivers" / f"{config['entry']}.py",
        f"bench_driver_{config['entry']}")
    scratch = tempfile.mkdtemp(prefix="bench-run-")
    tracer = Trace(scratch) if trace else None
    driver = driver_mod.Driver(config, mix, seed, devices, scratch)
    try:
        return _run(driver, spec, config, devices, seconds, tracer, t_start,
                    control, log)
    finally:
        driver.close()
        shutil.rmtree(scratch, ignore_errors=True)


def _run(driver, spec, config, devices, seconds, tracer, t_start, control,
         log):
    driver.setup()
    setup_s = time.perf_counter() - t_start
    log(f"setup done in {setup_s:.2f} s", file=sys.stderr)
    with _CompileCount() as compiles, _WindowCost() as cost:
        driver.window(seconds, tracer)
    if tracer is not None:
        tracer.stop()
    log(f"programs compiled or loaded in the window: {compiles.n}; "
        f"garbage collections {cost.gc_n} ({cost.gc_s:.4f} s, full "
        f"{cost.full_n}: {cost.full_s:.4f} s)", file=sys.stderr)
    mem = memory_peak(devices)
    driver.collect()
    data = driver.data()
    if data.get("rate_thirds"):
        log(f"window by thirds: {data['rate_thirds']}", file=sys.stderr)
    correct, checks = judge(config["checks"], driver.check())
    run = SimpleNamespace(setup_s=setup_s, trace=None, peaks=None, **data)
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": mem}
    result = {"correct": False, "attempted": data["attempted"],
              "failed": data["failed"]}
    breakdown = None
    if tracer is not None:
        run.trace = tracer.summary(data.get("kernels", {}))
        if devices[0].platform == "tpu":
            run.peaks = peaks_for(devices[0].device_kind)
        if run.trace is not None:
            device["busy_s"] = run.trace["busy_s"]
            device["window_s"] = run.trace["window_s"]
            breakdown = {"device_ops": run.trace["device_ops"],
                         "idle_gaps": run.trace["idle_gaps"]}
        metrics = read_metrics(spec.layer, run, "layer_metrics")
    else:
        metrics = read_metrics(spec.e2e, run, "e2e_metrics")
    result.update(correct=correct, metrics=metrics, device=device)
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["window"] = {"thirds": data.get("rate_thirds"),
                        "gc_n": cost.gc_n, "gc_s": cost.gc_s,
                        "gc_full_n": cost.full_n, "gc_full_s": cost.full_s,
                        "gc_full_max_s_at": list(cost.full_max),
                        "compiles": compiles.n}
    if data["ledger"]:
        recs = data["ledger"]
        result["window"]["ledger_ms_per_tick"] = {
            key: sum(r[key] for r in recs) / len(recs) / 1e6
            for key in recs[0] if key not in _LEDGER_IDS}
        result["window"]["ledger_ticks"] = len(recs)
        result["window"]["batch_docs"] = \
            sum(r["batch_docs"] for r in recs) / len(recs)
    if control:
        driver.use_control()
        ctl_correct, ctl_checks = judge(config["checks"], driver.check())
        result["control"] = {"correct": ctl_correct, "checks": ctl_checks}
    result["checks"] = checks
    return result, checks
