"""Closed-loop ack time, p95 over the frames acked in the window (send to
ack). Recorded only: under a closed loop the tail follows the window."""

from benchmark.harness import percentile


def read(run):
    if not run.latencies_ms:
        return None
    return percentile(run.latencies_ms, 0.95)
