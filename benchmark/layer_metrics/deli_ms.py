"""Per-op round, from the perop stage ledger: ms per round, mean over the
window's rounds, in the device deli lambda: the batched sequencer tick
and its tickets (deli)."""

from benchmark.harness import ledger_ms


def read(run):
    if not run.ledger or "deli" not in run.ledger[0]:
        return None  # a program whose ledger lacks the stage
    return ledger_ms(run, ("deli",))
