"""The benchmark's command:

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

One process holds the chip: set-up (data and weights from the seed, load
clients, warm-up of every shape the window uses), one measured window,
then the check of what the timed path produced against the plain
reference. The last line of standard output is the result; the numbers
compared, each beside its limit, end standard error. Exits nonzero, with
no result, where JAX finds no TPU or fewer chips than the cell asks for.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from benchmark import harness  # noqa: E402


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)
    spec = harness.resolve(args.workload)
    harness.enable_compile_cache()
    try:
        harness.chips(spec.cell["chips"])
    except harness.NoChip as err:
        print(f"benchmark: {err}", file=sys.stderr)
        return 3
    result, checks = harness.run_cell(
        args.workload, args.seed, args.seconds, bool(args.trace), T_START)
    for name, c in checks.items():
        print(f"check {name} {c['value']} limit {c['limit']}",
              file=sys.stderr)
    print(f"correct {result['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
