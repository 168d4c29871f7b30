"""Front door of the per-op path, from the perop stage ledger: ms per
round, mean over the window's rounds, of decoding every request of the
poll pass and submitting it (ingress_decode + submit)."""

from benchmark.harness import ledger_ms


def read(run):
    if not run.ledger or "submit" not in run.ledger[0]:
        return None  # a program whose ledger lacks the stage
    return ledger_ms(run, ("ingress_decode", "submit"))
