"""Per-op round, from the perop stage ledger: ms per round, mean over the
window's rounds, in the durable log's lambdas: the scriptorium
with its fsync, scribe and copier (log)."""

from benchmark.harness import ledger_ms


def read(run):
    if not run.ledger or "log" not in run.ledger[0]:
        return None  # a program whose ledger lacks the stage
    return ledger_ms(run, ("log",))
