"""The control comes out not correct, and sound runs of the traffic's
optional keys come out correct.

At SIM size on the CPU: the reference computed in bfloat16, put in the
program's place, on three seeds, against limits set between the
program's and the control's readings at this size
(``tests/data/limits-sim.mixed.json``); on the chip the same readings
are taken at the cell's own size by ``calibrate.py``.
"""
import json
import sys
import time
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH / "tests"))
from chipbench import harness as H  # noqa: E402
from test_chipbench_faults import _bench  # noqa: E402

DATA = BENCH / "tests" / "data"
LIMITS = json.loads((DATA / "limits-sim.mixed.json").read_text())


def _fails(numbers):
    return any(numbers[k] > LIMITS[k] for k in LIMITS)


def test_control_is_not_correct(tmp_path):
    rows = H.calibrate("sim.mixed", [2 ** 33 + 5, 2 ** 33 + 6, 2 ** 33 + 7],
                       2.0, bench_path=_bench(tmp_path), traffic_dir=DATA,
                       require_tpu=False)
    assert len(rows) == 3
    for r in rows:
        assert r["offloads_in_window"] > 0
        assert not _fails(r["program"]), r
        assert _fails(r["control_numbers"]), r
        # the control is at least three times the program on a number
        assert any(r["control_numbers"][k] >= 3 * r["program"][k]
                   for k in LIMITS), r


VARIANTS = {
    "camera": {"frame_interval_s": 0.3},
    "beta3": {"beta": 3},
    "burst_rejoin": {"start_offsets_s": [0.0, 0.0, 0.4],
                     "rebootstrap_every": 3},
}


@pytest.mark.parametrize("variant", list(VARIANTS))
def test_traffic_variant_is_correct(variant, tmp_path):
    traffic = json.loads((DATA / "sim-mixed.json").read_text())
    traffic.update(VARIANTS[variant])
    (tmp_path / "sim-mixed.json").write_text(json.dumps(traffic))
    out = H.run_cell("sim.mixed", 2 ** 33 + 9, 2.0, False,
                     t_start=time.perf_counter(),
                     bench_path=_bench(tmp_path), require_tpu=False,
                     limits=LIMITS, traffic_dir=tmp_path)
    assert out["attempted"] > 0
    assert out["correct"] is True, out["checks"]
