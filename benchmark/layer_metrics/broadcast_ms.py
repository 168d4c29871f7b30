"""Per-op round, from the perop stage ledger: ms per round, mean over the
window's rounds, in the broadcaster lambda: one ops event per
document, pushed to its writers (broadcast)."""

from benchmark.harness import ledger_ms


def read(run):
    if not run.ledger or "broadcast" not in run.ledger[0]:
        return None  # a program whose ledger lacks the stage
    return ledger_ms(run, ("broadcast",))
