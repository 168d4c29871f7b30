"""Seconds from process start to the window: imports, data from the seed,
client connects, load clients and the warm-up (compilation included)."""


def read(run):
    return run.setup_s
