#!/usr/bin/env python3
"""The chip benchmark of CkIO: one run of one cell.

    python3 perfbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

``BENCHMARK.json`` at the checkout's root names each cell's configuration
file and traffic mix; the mix names its driver (``drivers/<driver>.py``),
the cell's correctness limits are in ``cells/<cell>.json``, and each
per-layer metric is read by ``metrics/<metric>.py``. With ``--trace 0`` the
result holds the cell's end-to-end metrics; with ``--trace 1`` a profiler
trace of a short steady window gives its per-layer metrics.

The last line of standard output is the JSON result, printed only on a TPU
with the chips the cell asks for; the numbers compared with the plain
reference are the last lines of standard error and the result's last key.
"""
from __future__ import annotations

import time

STARTED = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from harness import checks
    from harness.device import NoChip
    from harness.main import log, run_cell

    try:
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace), started=STARTED)
    except NoChip as e:
        log(str(e))
        return 2
    compared = [checks.Check(k, v["value"], v["limit"])
                for k, v in result["checks"].items()]
    for line in checks.lines(compared):
        print(line, file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
