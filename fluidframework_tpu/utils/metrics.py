"""Metrics registry: counters, gauges, latency histograms with p99.

Reference parity: services-core/src/metricClient.ts (server metric seam),
connectionTelemetry.ts (client op round-trip latency), merge-tree's
accumTime/localTime micro counters (client.ts:45-55). TPU addition: a
registry ``snapshot()`` is a flat dict of floats so per-chip snapshots can
be summed across a mesh with one ``psum``
(fluidframework_tpu.parallel.mesh.aggregate_metrics).

Thread safety: the storm serving stack touches one registry from several
threads (the bridge pump, the WAL writer's drain callbacks, fanout
harvest), so every mutator and ``snapshot()`` take a per-metric lock —
one uncontended ``threading.Lock`` per observe, not a global registry
lock a hot histogram would serialize the whole assembly on.
"""

from __future__ import annotations

import gc
import math
import threading
from contextlib import nullcontext
import time
from collections import deque
from typing import Any, Iterable


class Counter:
    """Monotonic event count (merged ops, ticks, nacks...)."""

    __slots__ = ("value", "_lock")

    def __init__(self) -> None:
        self.value = 0.0
        self._lock = threading.Lock()

    def inc(self, amount: float = 1.0) -> None:
        with self._lock:
            self.value += amount


class Gauge:
    """Point-in-time level (queue depth, resident docs...)."""

    __slots__ = ("value", "_lock")

    def __init__(self) -> None:
        self.value = 0.0
        self._lock = threading.Lock()

    def set(self, value: float) -> None:
        with self._lock:
            self.value = float(value)

    def add(self, delta: float) -> None:
        with self._lock:
            self.value += delta


class Histogram:
    """Latency histogram over log-spaced buckets; O(1) observe, quantiles
    from linear interpolation within the winning bucket. Bounds default to
    1us..60s — wide enough for op-apply and device-tick latencies without
    per-sample storage (the "reservoir" the reference never needed because
    it never measured)."""

    __slots__ = ("_bounds", "_counts", "count", "total", "max", "min",
                 "_lock")

    def __init__(self, min_bound: float = 1e-6, max_bound: float = 60.0,
                 buckets_per_decade: int = 10) -> None:
        decades = math.log10(max_bound / min_bound)
        n = max(1, int(math.ceil(decades * buckets_per_decade)))
        self._bounds = [min_bound * (max_bound / min_bound) ** (i / n)
                        for i in range(1, n + 1)]
        self._counts = [0] * (n + 1)  # +1 overflow bucket
        self.count = 0
        self.total = 0.0
        self.max = 0.0
        self.min = math.inf
        self._lock = threading.Lock()

    def observe(self, value: float) -> None:
        lo, hi = 0, len(self._bounds)
        while lo < hi:
            mid = (lo + hi) // 2
            if value <= self._bounds[mid]:
                hi = mid
            else:
                lo = mid + 1
        with self._lock:
            self.count += 1
            self.total += value
            if value > self.max:
                self.max = value
            if value < self.min:
                self.min = value
            self._counts[lo] += 1

    def quantile(self, q: float) -> float:
        with self._lock:
            if self.count == 0:
                return 0.0
            rank = q * self.count
            seen = 0
            for i, c in enumerate(self._counts):
                if c and seen + c >= rank:
                    if i >= len(self._bounds):
                        return self.max
                    # Linear interpolation within the winning bucket:
                    # its c samples are assumed uniform over (lo, hi].
                    bucket_lo = self._bounds[i - 1] if i > 0 else 0.0
                    bucket_hi = self._bounds[i]
                    frac = (rank - seen) / c
                    est = bucket_lo + frac * (bucket_hi - bucket_lo)
                    # Bucket edges can overshoot the true extremes
                    # (e.g. a single observation mid-bucket).
                    return min(max(est, self.min), self.max)
                seen += c
            return self.max

    @property
    def mean(self) -> float:
        return self.total / self.count if self.count else 0.0


def percentile(sorted_values, q: float):
    """Nearest-rank percentile (index ``ceil(q*n) - 1``) of an
    ascending-sorted sequence — THE one definition every small-sample
    decomposition in this repo uses (StageLedger.attribution,
    TraceSpans.hop_quantiles, the bench hop columns), so p99 of
    identical samples agrees across surfaces."""
    n = len(sorted_values)
    if n == 0:
        return 0.0
    idx = max(0, math.ceil(q * n) - 1)
    return sorted_values[min(n - 1, idx)]


#: Stage order of one storm serving tick — the fixed shape of every
#: :class:`StageLedger` record (server/storm.py and the bridge pump fill
#: these). Every stage but those of ``OFF_THREAD_STAGES`` is serving-thread
#: time, so their sum is the attributed slice of the tick's wall clock.
STORM_STAGES = ("ingress_decode", "admission", "scatter", "device_dispatch",
                "readback", "wal_append", "wal_commit_wait", "ack_pack",
                "fanout_publish", "frame_wait", "ack_send", "idle_service",
                "service_pump", "cohort")

#: Stage order of one per-op serving round (server/bridge_host.py's pump
#: loop in batched cadence, server/routerlicious.py's lambdas): the wait
#: for the first request, decoding and submitting every request ready in
#: the poll pass, then one service pump — deli, the durable log
#: (scriptorium, scribe, copier), broadcast, the merger and its one
#: merge-host flush, and the other lambdas.
PEROP_STAGES = ("frame_wait", "ingress_decode", "submit", "deli", "log",
                "broadcast", "merge_flush", "service_other")

#: Stages that are the serving thread waiting for work, not doing it.
WAIT_STAGES = ("frame_wait",)

#: Storm stages timed off the serving thread: the pipelined commit-wait
#: (the WAL writer's fsync, amended into its record when the ack drains).
OFF_THREAD_STAGES = ("wal_commit_wait",)

#: Fields of every :class:`StageLedger` record that are not stage times.
RECORD_FIELDS = ("tick", "queue_depth", "batch_docs", "batch_ops", "wall",
                 "depth", "gc")

#: Prefix of every program span on the profiler's clock; the name after
#: it is the registry name of the same measurement
#: (``ff.storm.stage.scatter`` is the histogram ``storm.stage.scatter``).
SPAN_PREFIX = "ff."

_trace_cls: Any = None


def _trace_begin(name: str, args: dict):
    """A started ``jax.profiler.TraceAnnotation`` while a profile is being
    collected, else None: with the profiler off a span costs one
    ``is_enabled`` check, and JAX-free processes never trace."""
    global _trace_cls
    if _trace_cls is None:
        try:
            from jax.profiler import TraceAnnotation
        except ImportError:
            TraceAnnotation = False
        _trace_cls = TraceAnnotation
    if not _trace_cls or not _trace_cls.is_enabled():
        return None
    return _trace_cls(name, **args)


def _trace_end(tm) -> None:
    if tm is not None:
        tm.__exit__(None, None, None)


class ProgramSpan:
    """``with ProgramSpan("wal.fsync"):`` puts an ``ff.wal.fsync`` span on
    the profiler's timeline (nothing when no profile is collected). For
    work outside any :class:`StageLedger`, such as the WAL writer
    thread's batches."""

    __slots__ = ("name", "args", "_tm")

    def __init__(self, name: str, **args: Any) -> None:
        self.name = SPAN_PREFIX + name
        self.args = args
        self._tm = None

    def __enter__(self) -> "ProgramSpan":
        self._tm = _trace_begin(self.name, self.args)
        return self

    def __exit__(self, *exc: Any) -> None:
        _trace_end(self._tm)
        self._tm = None


class _GcClock:
    """Process-wide clock of Python garbage-collection pauses: the ns
    spent in collections so far (any thread), and an ``ff.process.gc``
    span per collection. :class:`StageLedger` records difference it."""

    def __init__(self) -> None:
        self.total_ns = 0
        self._t0: int | None = None
        self._tm = None
        self._installed = False

    def install(self) -> None:
        if not self._installed:
            self._installed = True
            gc.callbacks.append(self._on_gc)

    def _on_gc(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._tm = _trace_begin(SPAN_PREFIX + "process.gc",
                                    {"generation": info["generation"]})
            self._t0 = time.monotonic_ns()
        elif self._t0 is not None:
            self.total_ns += time.monotonic_ns() - self._t0
            self._t0 = None
            _trace_end(self._tm)
            self._tm = None


GC_CLOCK = _GcClock()


class _Stage:
    """One stage of a :class:`StageLedger` (see its ``stage``). Only the
    innermost open stage of a thread runs; the ones around it are
    suspended. ``ns`` is what it charged to the ledger's pending split
    since record cut ``cut`` (what :meth:`StageLedger.refund` takes
    back)."""

    __slots__ = ("_ledger", "name", "_args", "t0", "t1", "ns", "cut", "_tm",
                 "_open", "_running")

    def __init__(self, ledger: "StageLedger", name: str, args: dict,
                 since_ns: int | None) -> None:
        self._ledger = ledger
        self.name = name
        self._args = args
        self.t0 = since_ns
        self.t1 = 0
        self.ns = 0
        self.cut = -1
        self._tm = None
        self._open = False
        self._running = False

    @property
    def open(self) -> bool:
        return self._open

    def __enter__(self) -> "_Stage":
        led = self._ledger
        stack = led._stack()
        if stack:
            stack[-1]._suspend()
        self._tm = _trace_begin(led._span_prefix + self.name, self._args)
        now = time.monotonic_ns()
        if self.t0 is None:
            self.t0 = now
        self._open = self._running = True
        stack.append(self)
        return self

    def __exit__(self, *exc: Any) -> None:
        if not self._open:
            return
        now = self.t1 = time.monotonic_ns()
        self._open = False
        led = self._ledger
        if self._running:
            # A suspended stage (closed out of order, under a stage still
            # open) was charged up to its suspension: nothing more.
            self._running = False
            led._charge(self, now)
            _trace_end(self._tm)
            self._tm = None
        stack = led._stack()
        if stack and stack[-1] is self:
            stack.pop()
            if stack:
                stack[-1]._resume()
        elif self in stack:
            stack.remove(self)

    def _suspend(self) -> None:
        # A nested stage takes over: this one's time so far is charged,
        # and its span ends, so each instant counts in one stage only.
        self._running = False
        self._ledger._charge(self, time.monotonic_ns())
        _trace_end(self._tm)
        self._tm = None

    def _resume(self) -> None:
        self._tm = _trace_begin(self._ledger._span_prefix + self.name,
                                self._args)
        self.t0 = time.monotonic_ns()
        self._running = True


def maybe_stage(ledger: "StageLedger | None", name: str):
    """``ledger.stage(name)``, or a no-op context where there is none."""
    return ledger.stage(name) if ledger is not None else nullcontext()


class StageLedger:
    """Per-tick stage attribution: ONE fixed-shape record per serving
    tick — tick id, queue depth, batch size, a monotonic-ns split per
    pipeline stage, and the ns of garbage collection since the previous
    record (``gc``) — kept in a bounded ring buffer and mirrored into
    per-stage :class:`Histogram` s of a shared registry (so alfred's
    ``get_metrics`` exports ``<prefix>.<stage>.p50/p99`` and
    tools/monitor.py can render a live stage-attribution bar).

    Stages are timed with :meth:`stage`: at its edges it reads
    ``time.monotonic_ns`` and adds the ns to the pending split that the
    next :meth:`record` consumes, and it opens a profiler span
    ``ff.<prefix>.<stage>`` on the same edges, so the device trace and the
    ledger share one clock. Stages nest without double counting: a nested
    stage suspends the one around it. Charged intervals never overlap:
    each starts no earlier than the last cut or the end of the last
    charge, so the stages of one thread sum to at most the record's
    ``wall``, and ``wall`` minus that sum is the time no stage covered.
    ``gc`` overlaps the stages it interrupted and is not a stage.

    The record dict is intentionally flat and identical every tick
    (stages absent from a split map record 0 ns), so downstream consumers
    (bench columns, the monitor bar) never branch on shape.

    Pipelined ticks (round 14) overlap stages across tick boundaries —
    tick N's ``wal_commit_wait`` runs on the WAL writer thread while
    tick N+1's ``device_dispatch`` runs on the serving thread — so the
    per-stage splits of one tick can SUM past the wall clock it
    occupied. ``record`` therefore also takes the tick's exclusive
    wall-clock slice (``wall_ns``, the harvest-to-harvest cadence) and
    the serving pipeline depth; :meth:`attribution` reports wall time
    and an explicit ``overlap_ms`` instead of pretending the concurrent
    spans were sequential.
    """

    def __init__(self, stages: Iterable[str] = STORM_STAGES,
                 registry: "MetricsRegistry | None" = None,
                 prefix: str = "storm.stage", capacity: int = 1024) -> None:
        self.stages = tuple(stages)
        self.prefix = prefix
        self._span_prefix = f"{SPAN_PREFIX}{prefix}."
        self._ring: deque = deque(maxlen=max(1, capacity))
        self._lock = threading.Lock()
        self._local = threading.local()
        self._pending = dict.fromkeys(self.stages, 0)
        # _mark_ns: the last cut (record or clear); _covered_ns: where the
        # last charged interval ended; _cuts counts the cuts. The lock
        # guards them and the pending split: clear() may run on another
        # thread than the stages.
        self._mark_ns = self._covered_ns = time.monotonic_ns()
        self._cuts = 0
        GC_CLOCK.install()
        self._gc_mark = GC_CLOCK.total_ns
        self._hists = None
        self._wall_hist = None
        if registry is not None:
            self._hists = {s: registry.histogram(f"{prefix}.{s}")
                           for s in self.stages}
            self._wall_hist = registry.histogram(f"{prefix}.wall")

    def _charge(self, stage: _Stage, now: int) -> None:
        with self._lock:
            self._charge_locked(stage, now)

    def _charge_locked(self, stage: _Stage, now: int) -> None:
        # Time already charged, or before the last cut, is not charged
        # again.
        ns = now - max(stage.t0, self._covered_ns)
        if ns <= 0:
            return
        self._covered_ns = now
        self._pending[stage.name] += ns
        if stage.cut != self._cuts:
            stage.cut, stage.ns = self._cuts, 0
        stage.ns += ns

    def refund(self, stage: _Stage) -> None:
        """Take back what ``stage`` charged to the pending split: work
        that turned out to serve no tick (a frame shed before any tick
        took it). A record already cut keeps its share; the refunded time
        shows as the record's uncovered rest."""
        with self._lock:
            if stage.cut == self._cuts:
                self._pending[stage.name] -= stage.ns
            stage.ns = 0

    def pending(self) -> dict:
        """The split the next :meth:`record` will hold so far."""
        with self._lock:
            return dict(self._pending)

    def _stack(self) -> list:
        try:
            return self._local.stack
        except AttributeError:
            stack = self._local.stack = []
            return stack

    def stage(self, name: str, tick: int | None = None,
              since_ns: int | None = None) -> _Stage:
        """Context manager timing one stage of the next record (see the
        class doc). ``tick`` rides the profiler span as an argument;
        ``since_ns`` starts the ledger's count at an earlier
        ``time.monotonic_ns`` stamp (a receive time taken before the
        stage could open), though never before time already charged. It
        may also be entered and exited by hand across calls: the
        innermost open stage of the calling thread is
        :meth:`open_stage`."""
        if name not in self._pending:
            raise ValueError(f"unknown ledger stage: {name!r}")
        return _Stage(self, name, {} if tick is None else {"tick": tick},
                      since_ns)

    def open_stage(self) -> _Stage | None:
        """The calling thread's innermost open stage, if any."""
        stack = self._stack()
        return stack[-1] if stack else None

    def record(self, tick_id: int, queue_depth: int, batch_docs: int,
               batch_ops: int, splits_ns: dict | None = None,
               wall_ns: int | None = 0, depth: int = 0) -> dict:
        """Commit one tick's record: the pending split of the stages timed
        since the previous record, plus ``splits_ns`` (explicit splits;
        unknown keys are rejected — a typo'd stage would silently vanish
        from the attribution, and must fail under ``python -O`` too,
        hence no assert). ``wall_ns`` is the tick's exclusive wall-clock
        slice (0 = unknown, the pre-pipelining shape; None = the time
        since the previous record, or since :meth:`clear`); ``depth`` the
        serving pipeline depth that produced it."""
        splits_ns = splits_ns or {}
        unknown = set(splits_ns) - set(self.stages)
        if unknown:
            raise ValueError(f"unknown ledger stages: {sorted(unknown)}")
        stack = self._stack()
        with self._lock:
            now = time.monotonic_ns()
            if stack:
                # The cut is exact: a stage still open is charged up to
                # here.
                top = stack[-1]
                self._charge_locked(top, now)
                top.t0 = now
            pending = self._pending
            self._pending = dict.fromkeys(self.stages, 0)
            if wall_ns is None:
                wall_ns = now - self._mark_ns
            self._mark_ns = self._covered_ns = now
            self._cuts += 1
            gc_ns = GC_CLOCK.total_ns
            rec = {"tick": int(tick_id), "queue_depth": int(queue_depth),
                   "batch_docs": int(batch_docs),
                   "batch_ops": int(batch_ops), "wall": int(wall_ns),
                   "depth": int(depth), "gc": gc_ns - self._gc_mark}
            self._gc_mark = gc_ns
            for s in self.stages:
                rec[s] = int(pending[s] + splits_ns.get(s, 0))
            self._ring.append(rec)
        if self._wall_hist is not None and rec["wall"] > 0:
            self._wall_hist.observe(rec["wall"] / 1e9)
        if self._hists is not None:
            for s in self.stages:
                ns = rec[s]
                if ns > 0:
                    self._hists[s].observe(ns / 1e9)
        return rec

    def amend(self, rec: dict, stage: str, ns: int) -> None:
        """Backfill one stage of an already-committed record — the WAL
        commit-wait completes ticks after the record is cut (acks drain
        at the durability watermark, not at harvest)."""
        if stage not in self.stages:
            raise ValueError(f"unknown ledger stage: {stage!r}")
        rec[stage] = int(ns)
        if self._hists is not None and ns > 0:
            self._hists[stage].observe(ns / 1e9)

    def __len__(self) -> int:
        with self._lock:
            return len(self._ring)

    def clear(self) -> None:
        """Drop the ring and the pending split, and restart the wall
        clock (benches clear warm-up/compile ticks so the attribution
        window covers only the measured run); a stage open across the
        clear, on any thread, charges only its time after it. The
        registry histograms keep their cumulative view."""
        with self._lock:
            self._ring.clear()
            self._pending = dict.fromkeys(self.stages, 0)
            self._mark_ns = self._covered_ns = time.monotonic_ns()
            self._cuts += 1
            self._gc_mark = GC_CLOCK.total_ns

    def records(self) -> list[dict]:
        with self._lock:
            return [dict(r) for r in self._ring]

    def attribution(self) -> dict:
        """Per-stage share of attributed tick time over the ring window:
        {stage: {"share", "p50_ms", "p99_ms", "total_ms"}} plus a
        "_window" row (ticks covered, attributed vs total ns). The shares
        sum to 1.0 over stages with any time recorded. p50/p99 cover the
        ticks where the stage RAN (nonzero split) — the same population
        the registry histograms observe, so the two surfaces agree.

        When the records carry wall-clock slices (pipelined serving),
        each stage also reports ``of_wall`` — the fraction of real wall
        time it was active, which can sum PAST 1.0 across stages when
        they overlap — and "_window" reports the honest time budget:
        ``wall_ms`` (what the ticks actually occupied), ``overlap_ms``
        (attributed − wall, the concurrency the pipeline bought; 0 when
        stages ran sequentially) and ``pipeline_depth``. Summing the
        per-stage totals and calling it tick time double-counts under
        overlap — wall_ms is the denominator that does not lie."""
        recs = self.records()
        out: dict[str, Any] = {}
        if not recs:
            return {"_window": {"ticks": 0}}
        totals = {s: sum(r[s] for r in recs) for s in self.stages}
        grand = sum(totals.values()) or 1
        wall_total = sum(r.get("wall", 0) for r in recs)
        for s in self.stages:
            samples = sorted(r[s] for r in recs if r[s] > 0)
            out[s] = {
                "share": round(totals[s] / grand, 4),
                "p50_ms": round(percentile(samples, 0.50) / 1e6, 3),
                "p99_ms": round(percentile(samples, 0.99) / 1e6, 3),
                "total_ms": round(totals[s] / 1e6, 3),
            }
            if wall_total > 0:
                out[s]["of_wall"] = round(totals[s] / wall_total, 4)
        depths = [r.get("depth", 0) for r in recs if r.get("depth", 0) > 0]
        out["_window"] = {
            "ticks": len(recs),
            "attributed_ms": round(grand / 1e6, 3),
            "wall_ms": round(wall_total / 1e6, 3),
            "overlap_ms": round(max(0, grand - wall_total) / 1e6, 3)
            if wall_total > 0 else 0.0,
            "pipeline_depth": round(sum(depths) / len(depths), 2)
            if depths else 0,
            "mean_batch_docs": round(sum(r["batch_docs"] for r in recs)
                                     / len(recs), 1),
            "mean_queue_depth": round(sum(r["queue_depth"] for r in recs)
                                      / len(recs), 1),
        }
        return out


class CountedLRU:
    """Small bounded LRU map with hit/miss counters wired into a shared
    registry — the cache shape the serving hot path needs (storm cohort
    resolution, residency cold-handle lookups): O(1) get/put, strict
    entry bound, and an observable hit rate so a thrashing cache shows
    up in a metrics scrape instead of as unexplained tick time.

    NOT thread-safe by itself — callers on the serving thread use it
    bare; cross-thread users wrap it."""

    __slots__ = ("capacity", "_data", "_hits", "_misses")

    def __init__(self, capacity: int,
                 registry: "MetricsRegistry | None" = None,
                 prefix: str = "lru") -> None:
        from collections import OrderedDict
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self.capacity = capacity
        self._data: Any = OrderedDict()
        reg = registry if registry is not None else MetricsRegistry()
        self._hits = reg.counter(f"{prefix}.hits")
        self._misses = reg.counter(f"{prefix}.misses")

    def get(self, key, default=None):
        try:
            value = self._data[key]
        except KeyError:
            self._misses.inc()
            return default
        self._data.move_to_end(key)
        self._hits.inc()
        return value

    def put(self, key, value) -> None:
        data = self._data
        if key in data:
            data.move_to_end(key)
        data[key] = value
        if len(data) > self.capacity:
            data.popitem(last=False)  # evict least-recently-used

    def pop(self, key, default=None):
        return self._data.pop(key, default)

    def clear(self) -> None:
        self._data.clear()

    def __len__(self) -> int:
        return len(self._data)

    def __contains__(self, key) -> bool:
        return key in self._data

    @property
    def hits(self) -> int:
        return int(self._hits.value)

    @property
    def misses(self) -> int:
        return int(self._misses.value)


class MetricsRegistry:
    """Named metric bag. ``snapshot()`` flattens to {name: float}; counters
    and gauges sum across shards, histograms export count/mean/p50/p99/max.
    Creation is locked; per-metric mutation locks live on the metrics."""

    def __init__(self) -> None:
        self._metrics: dict[str, Any] = {}
        self._lock = threading.Lock()

    def counter(self, name: str) -> Counter:
        return self._get(name, Counter)

    def gauge(self, name: str) -> Gauge:
        return self._get(name, Gauge)

    def histogram(self, name: str, **kwargs: Any) -> Histogram:
        with self._lock:
            if name not in self._metrics:
                self._metrics[name] = Histogram(**kwargs)
            metric = self._metrics[name]
        assert isinstance(metric, Histogram), name
        return metric

    def _get(self, name: str, cls: type) -> Any:
        with self._lock:
            if name not in self._metrics:
                self._metrics[name] = cls()
            metric = self._metrics[name]
        assert isinstance(metric, cls), name
        return metric

    def snapshot(self) -> dict[str, float]:
        with self._lock:
            metrics = list(self._metrics.items())
        out: dict[str, float] = {}
        for name, metric in metrics:
            if isinstance(metric, (Counter, Gauge)):
                out[name] = metric.value
            else:
                out[f"{name}.count"] = float(metric.count)
                out[f"{name}.mean"] = metric.mean
                out[f"{name}.p50"] = metric.quantile(0.50)
                out[f"{name}.p99"] = metric.quantile(0.99)
                out[f"{name}.max"] = metric.max
        return out


default_registry = MetricsRegistry()
