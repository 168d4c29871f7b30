"""Durability, from the storm stage ledger: ms per tick, mean over the
window's ticks (wal_append + wal_commit_wait)."""

from benchmark.harness import ledger_ms


def read(run):
    return ledger_ms(run, ("wal_append", "wal_commit_wait"))
