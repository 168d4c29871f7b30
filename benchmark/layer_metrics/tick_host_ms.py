"""Fused tick, host side, from the storm stage ledger: ms per tick, mean over the
window's ticks (device_dispatch + readback)."""

from benchmark.harness import ledger_ms


def read(run):
    return ledger_ms(run, ("device_dispatch", "readback"))
