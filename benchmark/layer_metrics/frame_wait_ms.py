"""Load generator as the server sees it, from the storm stage ledger: ms
per tick the serving thread waited in the bridge poll with no frame to
serve (frame_wait), mean over the window's ticks."""

from benchmark.harness import ledger_ms


def read(run):
    if not run.ledger or "frame_wait" not in run.ledger[0]:
        return None  # a program whose ledger lacks the stage
    return ledger_ms(run, ("frame_wait",))
