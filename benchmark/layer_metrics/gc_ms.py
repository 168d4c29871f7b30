"""Host runtime, from the storm stage ledger: ms per tick of Python
garbage collection (the records' ``gc`` field, which overlaps the stages
it interrupted), mean over the window's ticks."""


def read(run):
    if not run.ledger or "gc" not in run.ledger[0]:
        return None  # a program whose ledger lacks the field
    return sum(r["gc"] for r in run.ledger) / len(run.ledger) / 1e6
