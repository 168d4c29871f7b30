"""The knee sweep of an open-loop cell: one run per offered rate, in one
process, each with a fresh seed. Sets the rate a paced cell runs at (0.8 x
the knee); it is not run by the benchmark's own runs, and needs an
open-loop cell in ``BENCHMARK.json`` (none is committed: PERF.md, Open
questions).

    python3 benchmark/sweep.py --workload <cell> --seconds <s> \
        --rates <frames/s per connection> ...

Prints one JSON line per rate: the end-to-end metrics, the median ack of
the frames due in each third of the window, and the frames never acked.
The knee is the highest rate whose last third's median ack is within 10 %
of its first third's (a backlog that stays flat) with every frame acked.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from benchmark import harness  # noqa: E402

FLAT = 1.10


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--rates", type=float, nargs="+", required=True)
    parser.add_argument("--seed", type=int, default=7_000_000_000)
    args = parser.parse_args(argv)
    spec = harness.resolve(args.workload)
    harness.enable_compile_cache()
    harness.chips(spec.cell["chips"])
    knee = None
    for i, rate in enumerate(args.rates):
        t0 = time.perf_counter()
        result, _checks = harness.run_cell(
            args.workload, args.seed + i, args.seconds, False, t0,
            traffic={"frames_per_s": rate})
        first, _mid, last = result["window"]["thirds"]["ack_ms_p50"]
        flat = (first is not None and last is not None
                and last <= FLAT * first and result["failed"] == 0)
        if flat:
            knee = rate
        print(json.dumps({
            "frames_per_s": rate, "flat": flat,
            "ack_ms_p50_thirds": result["window"]["thirds"]["ack_ms_p50"],
            "attempted": result["attempted"], "failed": result["failed"],
            "correct": result["correct"],
            "metrics": {k: v["value"] for k, v in result["metrics"].items()},
        }), flush=True)
    print(json.dumps({"knee_frames_per_s": knee}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
