#!/usr/bin/env python3
"""Run one cell of the on-chip benchmark once.

    python benchmarks/chip/run.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

from the root of a checkout.  The cell (a configuration under a traffic
mix) is looked up by name in ``BENCHMARK.json``; its configuration,
traffic and limit files and its per-layer readers are found by name
under ``benchmarks/chip``.  The last line of standard output is one JSON
object: ``correct``, ``attempted``, ``failed``, ``metrics``, ``device``
(and with ``--trace 1`` a ``breakdown``), then ``checks``, each compared
number beside its limit.  Without a TPU, or with fewer chips than the
cell asks for, it exits 1 and prints no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))

from chipbench import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = ap.parse_args(argv)
    try:
        out = harness.run_cell(a.workload, a.seed, a.seconds,
                               bool(a.trace), t_start=T_START)
    except harness.RunError as e:
        harness.log(f"chipbench: FAIL: {e}")
        return 1
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
