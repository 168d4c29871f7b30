"""Share of its HBM roofline the serving host's matrix tick reached in
the traced rounds, in %: the bytes its calls must move
(``matrix_bytes.py``, from the cell's shapes) over peak HBM bandwidth,
divided by the device time of its programs. The tick is plain XLA (no
Mosaic kernel), so its time is that of the XLA programs named after the
jitted function (``jit_matrix_tick``) within the traced window, read from
the trace the harness kept."""


def read(run):
    path_dir = getattr(run, "trace_dir", None)
    if not run.trace or not run.peaks or not path_dir:
        return None
    from benchmark import trace_reduce

    path = trace_reduce.find_xplane(path_dir)
    if path is None:
        return None
    profile = trace_reduce.load(path)
    window = None
    for _py, e in trace_reduce.host_events(profile):
        if e.name == trace_reduce.WINDOW_SPAN:
            window = (e.start_ns, e.start_ns + e.duration_ns)
    stable = run.kernels["matrix_apply"]
    calls, ivals = 0, []
    for _ops, modules in trace_reduce.device_ops(profile).values():
        for m in modules:
            if stable not in m.name:
                continue
            lo, hi = trace_reduce._clip(m.start_ns,
                                        m.start_ns + m.duration_ns, window)
            if hi > lo:
                calls += 1
                ivals.append((lo, hi))
    time_s = sum(hi - lo for lo, hi in trace_reduce.union(ivals)) / 1e9
    if not calls or time_s <= 0:
        return None
    moved = calls * run.kernel_bytes["matrix_apply"]
    return 100.0 * moved / run.peaks["hbm_bytes_per_s"] / time_s
