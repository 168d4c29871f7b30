"""Device idle time charged to the program's own spans, from a profile of
a traced window: the ``spans`` summary that ``trace_reduce.reduce()`` does
not carry yet (folding it in, with a reader of ``idle_unspanned_share``,
is an edit of ``trace_reduce.py``).

For each host event whose name starts with ``ff.`` (the program's spans:
stage-ledger stages, WAL batches, garbage collections) its ``host_s``
(time inside the traced window), ``count`` and ``idle_s`` (the device idle
time it overlaps, the mean over the chips that ran anything);
``idle_unspanned_s``, the device idle time that no ``ff.`` span overlaps;
and the same two for the span window (``span_window_s``,
``idle_in_span_window_s``, ``idle_unspanned_in_span_window_s``): the part
of the traced window from the first ledger-stage span's start to the last
one's end. A stage already open when the profiler starts, or still open
when it stops, leaves no span, so the window's edges read as unspanned up
to one stage each; the span window leaves them out. Busy and idle follow
the rules of ``trace_reduce.py``.
"""

from __future__ import annotations

from benchmark import trace_reduce

SPAN_PREFIX = "ff."


def _overlap(a: list, b: list) -> float:
    """Length of the intersection of two merged, sorted interval lists."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        lo = max(a[i][0], b[j][0])
        hi = min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def _clip_all(ivals: list, lo: float, hi: float) -> list:
    return [(max(a, lo), min(b, hi)) for a, b in ivals
            if min(b, hi) > max(a, lo)]


def spans(profile) -> dict:
    """The span summary (see the module doc) of the traced window of
    ``profile``."""
    hosts = trace_reduce.host_events(profile)
    per_dev = trace_reduce.device_ops(profile)
    window_spans = [e for _py, e in hosts
                    if e.name == trace_reduce.WINDOW_SPAN]
    if window_spans:
        w = max(window_spans, key=lambda e: e.duration_ns)
        window = (w.start_ns, w.start_ns + w.duration_ns)
    else:
        ends = [(e.start_ns, e.start_ns + e.duration_ns)
                for evs, _mods in per_dev.values() for e in evs]
        if not ends:
            return {"by_name": {}, "idle_unspanned_s": 0.0}
        window = (min(lo for lo, _ in ends), max(hi for _, hi in ends))
    idle = []
    for events, _modules in per_dev.values():
        busy = trace_reduce.union([
            iv for iv in (trace_reduce._clip(e.start_ns,
                                             e.start_ns + e.duration_ns,
                                             window) for e in events)
            if iv[1] > iv[0]])
        if not busy:
            continue  # a chip that ran nothing
        edges = [window[0]] + [x for iv in busy for x in iv] + [window[1]]
        idle.append([(lo, hi) for lo, hi in zip(edges[0::2], edges[1::2])
                     if hi > lo])
    by_name: dict[str, list] = {}
    for _py, e in hosts:
        if not e.name.startswith(SPAN_PREFIX):
            continue
        lo, hi = trace_reduce._clip(e.start_ns, e.start_ns + e.duration_ns,
                                    window)
        if hi > lo:
            by_name.setdefault(e.name, []).append((lo, hi))
    n = max(1, len(idle))
    out = {}
    for name, ivals in sorted(by_name.items()):
        covered = trace_reduce.union(ivals)
        out[name] = {
            "host_s": sum(hi - lo for lo, hi in ivals) / 1e9,
            "count": len(ivals),
            "idle_s": sum(_overlap(gaps, covered) for gaps in idle) / n / 1e9}
    spanned = trace_reduce.union([iv for ivals in by_name.values()
                                  for iv in ivals])

    def unspanned(gaps_of):
        return sum(sum(hi - lo for lo, hi in gaps) - _overlap(gaps, spanned)
                   for gaps in gaps_of) / n / 1e9

    result = {"by_name": out, "idle_unspanned_s": unspanned(idle)}
    stages = [iv for name, ivals in by_name.items() if ".stage." in name
              for iv in ivals]
    if stages:
        lo = min(a for a, _ in stages)
        hi = max(b for _, b in stages)
        inner = [_clip_all(gaps, lo, hi) for gaps in idle]
        result.update(
            span_window_s=(hi - lo) / 1e9,
            idle_in_span_window_s=sum(b - a for gaps in inner
                                      for a, b in gaps) / n / 1e9,
            idle_unspanned_in_span_window_s=unspanned(inner))
    return result
