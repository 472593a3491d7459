#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from.

    python benchmarks/chip/calibrate.py --workload <name> \
        --seeds 1,2,3 --seconds 6 [--out readings.jsonl]

In one process, for each seed: the cell's weights from that seed are
served through the same warmed grid and replica loop as a benchmark run
for a short window; then the numbers that decide ``correct`` are read
twice for the same sampled offloads and sessions — once for the program
(the lower reading) and once for the control, the reference computed in
bfloat16 in the program's place (the upper reading).  One JSON line per
seed; ``--dump DIR`` also keeps the compared detections of each seed.
The benchmark's own runs never run the control.
"""
import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

from chipbench import harness as H  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=6.0)
    ap.add_argument("--out", default=None)
    ap.add_argument("--dump", default=None,
                    help="directory for the compared detections, per seed")
    ap.add_argument("--bench", default=str(H.ROOT / "BENCHMARK.json"))
    ap.add_argument("--traffic-dir", default=str(H.BENCH_DIR / "traffic"))
    ap.add_argument("--cpu", action="store_true",
                    help="allow a run without a TPU (rehearsal only)")
    a = ap.parse_args(argv)
    rows = H.calibrate(a.workload, [int(s) for s in a.seeds.split(",")],
                       a.seconds, bench_path=Path(a.bench),
                       traffic_dir=Path(a.traffic_dir),
                       require_tpu=not a.cpu, dump=a.dump)
    out = open(a.out, "a") if a.out else None
    for r in rows:
        line = json.dumps(r)
        print(line, flush=True)
        if out:
            out.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
