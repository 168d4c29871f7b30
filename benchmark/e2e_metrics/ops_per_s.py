"""Ops completed over the whole window (acked durable on the storm path,
applied and readable on the others), per second of the window."""


def read(run):
    return run.ops / run.window_s
