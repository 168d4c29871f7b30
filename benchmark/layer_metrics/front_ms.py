"""Front door and admission, from the storm stage ledger: ms per tick, mean over the
window's ticks (ingress_decode + admission)."""

from benchmark.harness import ledger_ms


def read(run):
    return ledger_ms(run, ("ingress_decode", "admission"))
