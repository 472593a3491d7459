"""Compressed serving executables: the accuracy-gated calibration bench.

Runs quant.calibrate on the TRAINED sim server (benchmarks/common) over
the parkS / driveN calibration scenarios, records every candidate's
compression ratio and per-scenario rendering-F1 delta, and verifies the
quantized ServerModel compiles the IDENTICAL executable grid with zero
steady-state compiles.  Writes BENCH_quant.json at the repo root;
``--check`` enforces the deployment gates:

  * int8 compression ratio >= 3.5x over fp32 parameter bytes
  * a point SHIPS, and its F1 delta <= 0.005 on EVERY scenario
  * the quantized grid adds no executable keys beyond the fp32 grid,
    and serving after warmup incurs zero steady-state compiles
"""
from __future__ import annotations

import argparse
import json
from pathlib import Path

import numpy as np

import common
from repro import compile_cache
from repro.configs.vitdet_l import SIM
from repro.core.partition import RegionPlan
from repro.offload.simulator import ServerModel
from repro.quant import QuantSpec, calibrate as cal

DEFAULT_OUT = Path(__file__).resolve().parent.parent / "BENCH_quant.json"

RATIO_GATE = 3.5
# the smoke ladder skips the pruned candidate (head scoring runs eager
# forwards) — the gates it exercises are identical
SMOKE_CANDIDATES = (QuantSpec("int8", "fp16", 0), QuantSpec("int8", "fp32", 0))


def _point_row(p) -> dict:
    return {"spec": p.spec.name, "bytes": p.bytes,
            "ratio": round(p.ratio, 3),
            "deltas": {k: round(v, 5) for k, v in p.deltas.items()},
            "passed": p.passed}


def grid_invariance(params, spec: QuantSpec) -> dict:
    """Compile the fp32 and quantized grids over the same plan space and
    serve a few frames: same keys, zero steady compiles."""
    kw = dict(top_k=32, score_thresh=0.4, b_buckets=(1, 2))
    ref = ServerModel(SIM, params, **kw)
    space = ref.default_plan_space(betas=(2,))
    ref.warmup(space)

    s = ServerModel(SIM, params, quant=spec, **kw)
    s.warmup(space)
    part = s.part
    frames, _ = common.sv.make_clip("parkS", 3, size=common.SIZE, seed=5)
    mask = np.r_[np.ones(part.n_regions // 2, np.int32),
                 np.zeros(part.n_regions - part.n_regions // 2, np.int32)]
    s.infer(frames[0])
    s.infer(frames[1], mask, beta=2)
    s.infer_wave(np.stack(frames[:2]), [RegionPlan.from_mask(mask)] * 2,
                 beta=2)
    return {"spec": spec.name,
            "act_dtype": np.dtype(s.act_dtype).name,
            "ratio": round(s.quant_report["ratio"], 3),
            "fp32_keys": len(ref._fns), "quant_keys": len(s._fns),
            "keys_match": set(s._fns) == set(ref._fns),
            "new_keys": sorted(map(list, set(s._fns) - set(ref._fns))),
            "steady_compiles": int(s.stats.steady_compiles)}


def run_bench(smoke: bool = False, out: Path = DEFAULT_OUT) -> dict:
    server = common.get_server()
    params = server.params
    candidates = SMOKE_CANDIDATES if smoke else cal.DEFAULT_CANDIDATES
    n_frames = 3 if smoke else 8
    report_c = cal.calibrate(
        SIM, params, candidates=candidates, n_frames=n_frames,
        server_kw=dict(top_k=32, score_thresh=0.4))
    shipped = report_c.shipped
    grid = grid_invariance(
        params, shipped if shipped is not None else QuantSpec("int8"))
    report = {
        "meta": {"config": "vitdet-l/SIM",
                 "device": common.jax.default_backend(),
                 "smoke": smoke, "n_frames": n_frames,
                 "scenarios": list(report_c.scenarios),
                 "bound": report_c.bound},
        "calibration": {
            "shipped": shipped.name if shipped is not None else None,
            "bytes_fp32": report_c.bytes_fp32,
            "points": [_point_row(p) for p in report_c.points]},
        "grid": grid,
    }
    out.write_text(json.dumps(report, indent=2) + "\n")
    print(f"[bench_quant] wrote {out}")
    return report


def check_gates(report: dict) -> list:
    """The ISSUE acceptance gates; returns failure strings."""
    fails = []
    pts = report["calibration"]["points"]
    int8 = [p for p in pts if p["spec"].startswith("int8")]
    if not int8 or max(p["ratio"] for p in int8) < RATIO_GATE:
        fails.append(f"no int8 point reaches the {RATIO_GATE}x "
                     f"compression gate: {[(p['spec'], p['ratio']) for p in int8]}")
    shipped = report["calibration"]["shipped"]
    bound = report["meta"]["bound"]
    if shipped is None:
        fails.append("no candidate held the F1 bound — deployment stays "
                     "fp32")
    else:
        p = next(p for p in pts if p["spec"] == shipped)
        bad = {k: v for k, v in p["deltas"].items() if v > bound}
        if bad:
            fails.append(f"shipped point {shipped} breaks the bound: {bad}")
    g = report["grid"]
    if not g["keys_match"]:
        fails.append(f"quantized grid keys differ from fp32: "
                     f"new={g['new_keys']} "
                     f"({g['quant_keys']} vs {g['fp32_keys']})")
    if g["steady_compiles"]:
        fails.append(f"{g['steady_compiles']} steady-state compiles after "
                     "quantized warmup")
    for f in fails:
        print(f"[bench_quant] GATE FAIL {f}")
    if not fails:
        print(f"[bench_quant] gates ok: shipped={shipped} "
              f"ratio>={RATIO_GATE}x, deltas<={bound}, grid invariant, "
              "0 steady compiles")
    return fails


def run(ctx: dict) -> list:
    """benchmarks/run.py adapter: smoke settings, CSV rows."""
    out = Path(__file__).resolve().parent / "artifacts"
    out.mkdir(parents=True, exist_ok=True)
    rep = run_bench(smoke=True, out=out / "BENCH_quant.smoke.json")
    rows = []
    for p in rep["calibration"]["points"]:
        worst = max(p["deltas"].values()) if p["deltas"] else float("nan")
        rows.append((f"bench_quant/{p['spec']}", p["ratio"],
                     f"worst_dF1={worst:.4f} passed={p['passed']}"))
    g = rep["grid"]
    rows.append((f"bench_quant/grid/{g['spec']}", g["steady_compiles"],
                 f"keys_match={g['keys_match']} act={g['act_dtype']}"))
    ctx["bench_quant"] = rows
    return rows


def main(argv=None) -> int:
    compile_cache.enable()
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true",
                    help="2-candidate int8 ladder, 3 frames (CI lane)")
    ap.add_argument("--out", type=Path, default=None,
                    help=f"output JSON path (default {DEFAULT_OUT}; "
                         "--check runs default to benchmarks/artifacts)")
    ap.add_argument("--check", action="store_true",
                    help="enforce the deployment gates (ratio, F1 bound, "
                         "grid invariance, steady compiles); exit 1 on "
                         "failure")
    args = ap.parse_args(argv)
    out = args.out
    if out is None:
        if args.check:
            out = Path(__file__).resolve().parent / "artifacts" \
                / "BENCH_quant.check.json"
            out.parent.mkdir(parents=True, exist_ok=True)
        else:
            out = DEFAULT_OUT
    rep = run_bench(smoke=args.smoke, out=out)
    for p in rep["calibration"]["points"]:
        print(f"  {p['spec']:>12}: ratio {p['ratio']:5.2f}x  deltas "
              f"{p['deltas']}  {'PASS' if p['passed'] else 'fail'}")
    print(f"  shipped: {rep['calibration']['shipped']}")
    g = rep["grid"]
    print(f"  grid[{g['spec']}]: keys_match={g['keys_match']} "
          f"steady_compiles={g['steady_compiles']} act={g['act_dtype']}")
    if args.check:
        return 1 if check_gates(rep) else 0
    return 0


if __name__ == "__main__":
    import sys
    sys.exit(main())
