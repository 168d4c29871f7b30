"""The readings that set each limit: the program's and the control's,
over many seeds in one process (set-up is long; the chip compiles once).

    python3 benchmark/controls.py --workload <cell> --seconds <s> \
        --seeds <n> [<n> ...]

Prints one JSON line per seed: the program's numbers compared ("program")
and the control's ("control"), the control having taken the program's
place in the same check (``control_correct``), plus ``correct`` and the
end-to-end metrics. Not run by the benchmark's own runs.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from benchmark import harness  # noqa: E402


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--seeds", type=int, nargs="+", required=True)
    args = parser.parse_args(argv)
    spec = harness.resolve(args.workload)
    harness.enable_compile_cache()
    harness.chips(spec.cell["chips"])
    for seed in args.seeds:
        t0 = time.perf_counter()
        result, checks = harness.run_cell(
            args.workload, seed, args.seconds, False, t0, control=True)
        control = result["control"]
        print(json.dumps({
            "seed": seed, "correct": result["correct"],
            "program": {k: c["value"] for k, c in checks.items()},
            "control_correct": control["correct"],
            "control": {k: c["value"]
                        for k, c in control["checks"].items()},
            "metrics": {k: m["value"]
                        for k, m in result["metrics"].items()},
            "window": result["window"],
            "attempted": result["attempted"], "failed": result["failed"],
            "memory_peak_bytes": result["device"]["memory_peak_bytes"]}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
