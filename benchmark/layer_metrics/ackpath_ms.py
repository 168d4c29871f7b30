"""Acks and fan-out, from the storm stage ledger: ms per tick, mean over the
window's ticks (ack_pack + fanout_publish)."""

from benchmark.harness import ledger_ms


def read(run):
    return ledger_ms(run, ("ack_pack", "fanout_publish"))
