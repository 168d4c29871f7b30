"""Per-op round, from the perop stage ledger: ms per round, mean over the
window's rounds, in the merger lambda and its one merge-host
flush, the matrix tick (merge_flush)."""

from benchmark.harness import ledger_ms


def read(run):
    if not run.ledger or "merge_flush" not in run.ledger[0]:
        return None  # a program whose ledger lacks the stage
    return ledger_ms(run, ("merge_flush",))
