"""A run with the timed path broken underneath comes out not correct.

The harness's look for a chip is skipped and the rest of a run is
driven at SIM size on the CPU: set-up, the closed-loop window, the
reference and the comparison against the limits of
``vitdet-l.mixed-reuse``.  One run is sound; each of the others plants
one fault the served cell can have: a cache (the session state) left
unrefreshed, half of a wave's rows answered with another row's result,
and one answer altered where it is produced.
"""
import json
import sys
import time
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parents[1]
sys.path.insert(0, str(BENCH))
from chipbench import harness as H  # noqa: E402

LIMITS = json.loads((BENCH / "limits" / "vitdet-l.mixed-reuse.json")
                    .read_text())


def _bench(tmp_path):
    e2e = [{"name": n, "unit": u} for n, u in (
        ("offload_p90_ms", "ms"), ("offload_p50_ms", "ms"),
        ("frames_per_s", "frames/s"), ("setup_s", "s"))]
    bench = {"configs": [{"name": "sim",
                          "file": "benchmarks/chip/tests/data/sim.json"}],
             "workloads": [{"name": "sim.mixed", "config": "sim",
                            "traffic": "sim-mixed", "chips": 1}],
             "end_to_end": e2e, "per_layer": []}
    p = tmp_path / "BENCHMARK.json"
    p.write_text(json.dumps(bench))
    return p


def _stale_cache(sm):
    refresh = sm._refresh_caches

    def only_first(caches, *a, **kw):
        if all(c is None or not c.warm for c in caches):
            refresh(caches, *a, **kw)
    sm._refresh_caches = only_first


def _wrap_wave(sm, edit):
    infer = sm.infer_wave

    def broken(*a, **kw):
        pending = infer(*a, **kw)
        edit(pending)
        return pending
    sm.infer_wave = broken


def _half_wave(pending):
    # edits go through the host, so that the fault itself compiles
    # nothing inside the window
    import jax.numpy as jnp
    h = (pending.B + 1) // 2
    if pending.B > 1:
        boxes, scores = np.array(pending.boxes), np.array(pending.scores)
        boxes[h:], scores[h:] = boxes[0], scores[0]
        pending.boxes, pending.scores = jnp.asarray(boxes), \
            jnp.asarray(scores)


def _altered(pending):
    import jax.numpy as jnp
    boxes = np.array(pending.boxes)
    boxes[0, 0, 0] += 64.0
    pending.boxes = jnp.asarray(boxes)


FAULTS = {"sound": None, "stale_cache": _stale_cache,
          "half_wave": lambda sm: _wrap_wave(sm, _half_wave),
          "altered_answer": lambda sm: _wrap_wave(sm, _altered)}


@pytest.mark.parametrize("fault", list(FAULTS))
def test_fault_is_not_correct(fault, tmp_path):
    hooks = {"after_setup": FAULTS[fault]} if FAULTS[fault] else None
    out = H.run_cell("sim.mixed", 2 ** 33 + 3, 2.0, False,
                     t_start=time.perf_counter(),
                     bench_path=_bench(tmp_path), require_tpu=False,
                     limits=LIMITS, hooks=hooks,
                     traffic_dir=BENCH / "tests" / "data")
    assert out["attempted"] > 0
    assert list(out)[-1] == "checks"
    assert out["correct"] is (fault == "sound"), out["checks"]
