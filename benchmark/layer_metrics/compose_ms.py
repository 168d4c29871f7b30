"""Compose, from the storm stage ledger: ms per tick, mean over the
window's ticks (scatter)."""

from benchmark.harness import ledger_ms


def read(run):
    return ledger_ms(run, ("scatter",))
