"""Reduce a JAX profiler trace (``.xplane.pb``) to the benchmark's device
numbers: busy and idle time per chip, kernel time by stable name, the
device ops that took most time, and the longest idle gaps labelled by what
the host was doing.

Rules (the same for every PR, so that no PR can move them):

* A device is a plane named ``/device:TPU:<n>``. Its operations are the
  events of its ``XLA Ops`` line (``XLA Modules`` where a trace has no op
  line).
* The traced window is the host span named :data:`WINDOW_SPAN`, which the
  harness opens around the traced ticks. Device intervals are clipped to
  it. A trace without that span uses the extent of its device events.
* Busy time of a device is the length of the union of its op intervals;
  ``busy_s`` is the mean over the devices that ran anything.
* An op is named by its HLO instruction (``fusion.3`` for the event
  ``%fusion.3 = s32[...] fusion(...)``).
* A kernel is a Mosaic custom call (``tpu_custom_call``) that runs inside
  a program (an ``XLA Modules`` event) whose name contains the kernel's
  stable name, the jitted function that calls it: the Pallas kernel's own
  name does not reach the trace. Kernel time is the sum of those ops'
  durations, over every device; ``count`` is the number of calls.
* An idle gap is labelled by the host event that overlaps it most,
  preferring the Python-level events (``python`` line) to runtime threads.
"""

from __future__ import annotations

import glob
import os
import re

WINDOW_SPAN = "bench.trace"
KERNEL_OP = "tpu_custom_call"
_DEVICE = re.compile(r"^/device:TPU:\d+$")
_OP_LINES = ("XLA Ops", "XLA Modules")


def find_xplane(log_dir: str) -> str | None:
    found = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    return found[-1] if found else None


def load(path: str):
    from jax.profiler import ProfileData

    return ProfileData.from_file(path)


def union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    """Merged, sorted intervals covering the same points."""
    merged: list[list[float]] = []
    for lo, hi in sorted(intervals):
        if merged and lo <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], hi)
        else:
            merged.append([lo, hi])
    return [(lo, hi) for lo, hi in merged]


def _clip(lo, hi, window):
    if window is None:
        return lo, hi
    return max(lo, window[0]), min(hi, window[1])


def short_name(name: str) -> str:
    """``fusion.3`` for an HLO op event ``%fusion.3 = s32[...] ...``."""
    if name.startswith("%") and " = " in name:
        return name[1:name.index(" = ")]
    return name


def device_ops(profile) -> dict[str, tuple[list, list]]:
    """{device plane name: (op events, program events)} of each TPU
    plane."""
    out = {}
    for plane in profile.planes:
        if not _DEVICE.match(plane.name):
            continue
        lines = {line.name: list(line.events) for line in plane.lines}
        ops = next((lines[n] for n in _OP_LINES if n in lines), [])
        out[plane.name] = (ops, lines.get("XLA Modules", []))
    return out


def _program_of(modules: list, start: float) -> str:
    """Name of the program event that holds ``start`` ('' for none)."""
    for m in modules:
        if m.start_ns <= start <= m.start_ns + m.duration_ns:
            return m.name
    return ""


def host_events(profile) -> list[tuple[bool, object]]:
    """(on the python line, event) for every event of the host planes."""
    out = []
    for plane in profile.planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            out.extend((line.name == "python", e) for e in line.events)
    return out


def reduce(profile, kernels: dict[str, str] | None = None,
           top: int = 10) -> dict:
    """The trace summary the per-layer readers use.

    ``kernels`` maps a kernel's benchmark name to its stable name (the
    jitted function whose program runs it). Returns ``window_s``, ``busy_s`` (mean over active devices),
    ``devices`` (per device: busy_s, ops), ``kernels`` ({name: {time_s,
    count}} for kernels that ran), ``device_ops`` and ``idle_gaps`` (the
    ``top`` largest, as [name, seconds])."""
    hosts = host_events(profile)
    spans = [e for _py, e in hosts if e.name == WINDOW_SPAN]
    per_dev = device_ops(profile)
    window = None
    if spans:
        span = max(spans, key=lambda e: e.duration_ns)
        window = (span.start_ns, span.start_ns + span.duration_ns)
    else:
        ends = [(e.start_ns, e.start_ns + e.duration_ns)
                for evs, _mods in per_dev.values() for e in evs]
        if ends:
            window = (min(lo for lo, _ in ends), max(hi for _, hi in ends))
    if window is None:
        return {"window_s": 0.0, "busy_s": 0.0, "devices": {},
                "kernels": {}, "device_ops": [], "idle_gaps": []}
    window_ns = window[1] - window[0]

    devices, op_time, kern = {}, {}, {}
    gaps = []
    for dev, (events, modules) in sorted(per_dev.items()):
        ivals = []
        for e in events:
            lo, hi = _clip(e.start_ns, e.start_ns + e.duration_ns, window)
            if hi <= lo:
                continue
            ivals.append((lo, hi))
            name = short_name(e.name)
            op_time[name] = op_time.get(name, 0.0) + (hi - lo)
            if kernels and KERNEL_OP in e.name:
                program = _program_of(modules, e.start_ns)
                for kname, stable in kernels.items():
                    if stable in program:
                        slot = kern.setdefault(kname, {"time_s": 0.0,
                                                       "count": 0})
                        slot["time_s"] += (hi - lo) / 1e9
                        slot["count"] += 1
        merged = union(ivals)
        busy = sum(hi - lo for lo, hi in merged)
        if busy > 0:
            devices[dev] = {"busy_s": busy / 1e9, "ops": len(ivals)}
        edges = [window[0]] + [x for iv in merged for x in iv] + [window[1]]
        for lo, hi in zip(edges[0::2], edges[1::2]):
            if hi > lo:
                gaps.append((lo, hi))
    busy_s = (sum(d["busy_s"] for d in devices.values()) / len(devices)
              if devices else 0.0)
    gaps.sort(key=lambda g: g[0] - g[1])
    labelled = [[_host_label(hosts, lo, hi), (hi - lo) / 1e9]
                for lo, hi in gaps[:top]]
    ops = sorted(op_time.items(), key=lambda kv: -kv[1])[:top]
    return {"window_s": window_ns / 1e9, "busy_s": busy_s,
            "devices": devices, "kernels": kern,
            "device_ops": [[name, ns / 1e9] for name, ns in ops],
            "idle_gaps": labelled}


def _host_label(hosts, lo, hi) -> str:
    """The host event (other than the window span) that overlaps the gap
    most, a Python-level one where any overlaps: what the host was doing
    while the device idled."""
    best = (False, 0.0, "host: nothing traced")
    for on_python, e in hosts:
        if e.name == WINDOW_SPAN:
            continue
        ov = min(hi, e.start_ns + e.duration_ns) - max(lo, e.start_ns)
        if ov > 0 and (on_python, ov) > best[:2]:
            best = (on_python, ov, e.name)
    return best[2]
