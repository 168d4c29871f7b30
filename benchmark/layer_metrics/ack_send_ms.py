"""Acks and fan-out, from the storm stage ledger: ms per tick spent
encoding and pushing acks once their tick is durable (ack_send), mean
over the window's ticks."""

from benchmark.harness import ledger_ms


def read(run):
    if not run.ledger or "ack_send" not in run.ledger[0]:
        return None  # a program whose ledger lacks the stage
    return ledger_ms(run, ("ack_send",))
