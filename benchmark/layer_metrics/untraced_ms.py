"""Serving thread, from the storm stage ledger: ms per tick of the tick's
wall clock (harvest to harvest) that no serving-thread stage covered,
mean over the window's ticks. Only a ledger that times every stage on
the serving thread's clock (its records carry ``gc``) closes this
account."""


def read(run):
    if not run.ledger or "gc" not in run.ledger[0]:
        return None  # a program whose ledger does not time every stage
    from fluidframework_tpu.utils.metrics import (OFF_THREAD_STAGES,
                                                  RECORD_FIELDS)

    stages = [k for k in run.ledger[0]
              if k not in RECORD_FIELDS and k not in OFF_THREAD_STAGES]
    return sum(r["wall"] - sum(r[s] for s in stages)
               for r in run.ledger) / len(run.ledger) / 1e6
