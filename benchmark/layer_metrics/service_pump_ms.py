"""Acks and fan-out, from the storm stage ledger: ms per tick the round
spends draining the service's lambdas and the fan-out rooms into their
in-process subscribers before it composes (service_pump), mean over the
window's ticks."""

from benchmark.harness import ledger_ms


def read(run):
    if not run.ledger or "service_pump" not in run.ledger[0]:
        return None  # a program whose ledger lacks the stage
    return ledger_ms(run, ("service_pump",))
