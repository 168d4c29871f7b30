"""Durability, from the group-commit WAL's counters: ms per tick its
writer thread spent in fsync over the window (the storm controller's
``wal_fsync_ns`` over its ticks)."""


def read(run):
    c = run.counters or {}
    if "wal_fsync_ns" not in c or not c.get("ticks"):
        return None
    return c["wal_fsync_ns"] / c["ticks"] / 1e6
