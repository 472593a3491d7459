"""Backbone hot-path benchmark — tracks the perf trajectory of the ViTDet
forward from this PR onward.

Emits ``BENCH_backbone.json`` with:

  * ``backbone``: us/call of the jitted ``forward_features`` for the
    full-resolution workload and the mixed-resolution workload at every
    restoration point beta.  Off-TPU the default is the XLA backend
    ONLY: pallas runs in INTERPRET mode there — a correctness path
    ~2-3x slower that only inflates bench wall time — so it must be
    opted back in with ``--backends pallas,xla`` (on TPU both backends
    are benched by default; ``meta.backends``/``meta.interpret`` record
    what ran);
  * ``server_infer``: us/call of ``ServerModel.infer`` on a fig5-style
    workload (object-free regions downsampled, per-frame calls) with the
    jitted bucketed (n_low, beta) cache vs the same model run eagerly —
    what the bucketed jit cache actually buys per frame.

Standalone:  python benchmarks/bench_backbone.py [--smoke] [--out PATH]
Harness:     picked up by benchmarks/run.py as the ``bench_backbone``
suite (smoke settings).
"""
from __future__ import annotations

import argparse
import json
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro import compile_cache
from repro.configs.vitdet_l import SIM
from repro.core import partition as pt
from repro.core import vit_backbone as vb
from repro.data import synthetic_video as sv
from repro.models import registry
from repro.offload import motion as mo
from repro.offload.simulator import ServerModel
from repro.quant import QuantSpec, ptq

DEFAULT_OUT = Path(__file__).resolve().parent.parent / "BENCH_backbone.json"

# compressed weight lanes benched alongside fp32 (repro.quant) — prune=0
# so every lane runs the identical SIM config/shapes and rows differ
# only in the executed dtype
QUANT_SPECS = (QuantSpec("int8", "fp32", 0), QuantSpec("int8", "fp16", 0),
               QuantSpec("fp16"))


def default_backends() -> tuple:
    """Both backends on TPU; XLA only elsewhere (pallas-interpret is a
    parity path ~2-3x slower on CPU — opt in with --backends)."""
    if jax.default_backend() == "tpu":
        return ("xla", "pallas")
    return ("xla",)


def _timer(fn, *args, reps: int = 5, warmup: int = 1) -> float:
    """Median wall-time per call in us (blocks on async dispatch)."""
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts) * 1e6)


def bench_backbone(params, img, part, reps: int, backends,
                   dtype: str = "fp32") -> list:
    """forward_features us/call: full-res + mixed at each beta, per
    backend.  ``dtype`` tags the rows ("fp32" or a repro.quant spec
    name); the params are expected to already carry that compression."""
    rows = []
    n_low = part.n_regions // 2
    mask = np.zeros(part.n_regions, np.int32)
    mask[:n_low] = 1
    fi, li = (jnp.asarray(x) for x in pt.mask_to_region_ids(mask, n_low))

    for backend in backends:
        full_fn = jax.jit(
            lambda p, i, _b=backend: vb.forward_features(SIM, p, i,
                                                         backend=_b))
        us = _timer(full_fn, params, img, reps=reps)
        rows.append({"workload": "full", "beta": None, "n_low": 0,
                     "backend": backend, "dtype": dtype, "us_per_call": us})
        for beta in range(SIM.vit.n_subsets + 1):
            fn = jax.jit(
                lambda p, i, a, b, _beta=beta, _b=backend:
                vb.forward_features(SIM, p, i, a, b, _beta, backend=_b))
            us = _timer(fn, params, img, fi, li, reps=reps)
            rows.append({"workload": "mixed", "beta": beta, "n_low": n_low,
                         "backend": backend, "dtype": dtype,
                         "us_per_call": us})

    # the padded serving hot path (PlanLayout-driven, what ServerModel
    # executes) — on the pallas backend this runs the fused
    # prologue/epilogue kernels (kernels/fused_serving)
    states = np.zeros((part.n_regions,), np.int8)
    states[:n_low] = pt.LOW
    lb = pt.length_bucket(pt.plan_n_windows(pt.RegionPlan(states), part),
                          pt.length_bucket_set(part))
    lay = pt.plan_layout(states, lb, part)
    layout = {k: jnp.asarray(getattr(lay, k))
              for k in ("win_src", "win_dst", "low_src", "low_ids",
                        "reuse_ids", "out_src", "out_map")}
    layout["nw"] = jnp.asarray([lay.nw], jnp.int32)
    for backend in backends:
        for beta in (1, 2):
            fn = jax.jit(
                lambda p, i, _beta=beta, _b=backend:
                vb.forward_features(SIM, p, i, beta=_beta, layout=layout,
                                    backend=_b))
            us = _timer(fn, params, img, reps=reps)
            rows.append({"workload": "padded", "beta": beta,
                         "n_low": n_low, "backend": backend,
                         "dtype": dtype, "us_per_call": us})
    return rows


def bench_quant_lanes(params, img, part, reps: int, backends) -> list:
    """Per-dtype rows for the compressed weight lanes: the SAME
    workloads as the fp32 pass, on params compressed to each
    ``QUANT_SPECS`` point (int8 weights run the quantized GEMM lane;
    fp16 runs the half-cast tree)."""
    rows = []
    for spec in QUANT_SPECS:
        _, cparams, rep = ptq.compress(SIM, params, spec)
        lane = bench_backbone(cparams, img, part, reps, backends,
                              dtype=spec.name)
        for r in lane:
            r["ratio"] = round(rep["ratio"], 3)
        rows.extend(lane)
    return rows


def bench_server_infer(params, n_frames: int, reps: int) -> dict:
    """fig5-style workload: per-frame ServerModel.infer, jitted bucketed
    cache vs the untraced (eager) path."""
    frames, gts = sv.make_clip("walkS", n_frames, size=SIM.vit.img_size[0],
                               seed=31)
    part = vb.vit_partition(SIM)
    patch = SIM.vit.patch_size
    masks = [(mo.region_density(g, part, patch) == 0).astype(np.int32)
             for g in gts]
    beta = 2

    def per_frame_us(server, reps):
        # warm up every (n_low, beta) bucket once, then time per-frame calls
        for f, m in zip(frames, masks):
            server.infer(f, m if m.sum() else None, beta if m.sum() else 0)
        ts = []
        for _ in range(reps):
            t0 = time.perf_counter()
            for f, m in zip(frames, masks):
                server.infer(f, m if m.sum() else None,
                             beta if m.sum() else 0)
            ts.append((time.perf_counter() - t0) / len(frames))
        return float(np.median(ts) * 1e6)

    jit_us = per_frame_us(ServerModel(SIM, params), reps)
    eager_us = per_frame_us(ServerModel(SIM, params, jit=False),
                            max(1, reps // 2))
    return {"workload": f"fig5-style walkS x{n_frames} frames, beta={beta}",
            "jit_us": jit_us, "eager_us": eager_us,
            "speedup": eager_us / jit_us if jit_us else float("nan")}


def run_bench(smoke: bool = False, out: Path = DEFAULT_OUT,
              backends=None) -> dict:
    # smoke keeps full-strength reps: compiles dominate smoke wall time
    # anyway, and median-of-2 timings are too noisy for the 1.15x
    # regression gate on shared/virtualised CPUs
    reps = 5
    n_frames = 2 if smoke else 6
    backends = tuple(backends) if backends else default_backends()
    params = registry.init_params(SIM, jax.random.PRNGKey(0))
    img = jax.random.uniform(jax.random.PRNGKey(1),
                             (1, *SIM.vit.img_size, 3))
    part = vb.vit_partition(SIM)

    report = {
        "meta": {
            "config": "vitdet-l/SIM",
            "device": jax.default_backend(),
            "backends": list(backends),
            "interpret": ("pallas" in backends
                          and jax.default_backend() != "tpu"),
            "smoke": smoke,
            "img_size": list(SIM.vit.img_size),
            "n_regions": part.n_regions,
        },
        "backbone": (bench_backbone(params, img, part, reps, backends)
                     + bench_quant_lanes(params, img, part, reps, backends)),
        "server_infer": bench_server_infer(params, n_frames, reps),
    }
    out.write_text(json.dumps(report, indent=2) + "\n")
    print(f"[bench_backbone] wrote {out}")
    return report


def check_regressions(report: dict, baseline: Path = DEFAULT_OUT,
                      tol: float = 1.15, tol_quant: float = 1.6) -> list:
    """Regression gate: compare fresh ``backbone`` rows against the
    committed baseline per (workload, beta, backend, dtype); a row more
    than ``tol``x slower is a failure.  Rows missing from the baseline
    and baselines from a different device kind are skipped (the
    committed numbers only bind the machine class that produced them).
    Pre-quant baselines have no dtype field — their rows default to
    "fp32" so old baselines keep matching.  The compressed lanes get
    the wider ``tol_quant`` band: their long emulated-fp16/int8 CPU
    calls jitter 20-40% run-to-run, so 1.15x would fire on noise — the
    regressions this gate exists to catch on those rows (steady-state
    retracing, dispatch falling off the quantized GEMM lane) are >1.6x
    shifts."""
    try:
        base = json.loads(Path(baseline).read_text())
    except (OSError, ValueError):
        print(f"[bench_backbone] no readable baseline at {baseline} — "
              "check skipped")
        return []
    if base.get("meta", {}).get("device") != report["meta"]["device"]:
        print(f"[bench_backbone] baseline device "
              f"{base.get('meta', {}).get('device')!r} != current "
              f"{report['meta']['device']!r} — check skipped")
        return []
    floors = {(r["workload"], r["beta"], r["backend"],
               r.get("dtype", "fp32")): r["us_per_call"]
              for r in base.get("backbone", [])}
    fails = []
    for r in report["backbone"]:
        key = (r["workload"], r["beta"], r["backend"],
               r.get("dtype", "fp32"))
        floor = floors.get(key)
        if floor is None:
            continue
        t = tol if r.get("dtype", "fp32") == "fp32" else tol_quant
        if r["us_per_call"] > floor * t:
            fails.append(f"{key}: {r['us_per_call']:.0f} us > "
                         f"{t:.2f}x baseline {floor:.0f} us")
    for f in fails:
        print(f"[bench_backbone] REGRESSION {f}")
    if not fails:
        print(f"[bench_backbone] check ok: {len(report['backbone'])} rows "
              f"within {tol:.2f}x (fp32) / {tol_quant:.2f}x (compressed) "
              "of baseline")
    return fails


def run(ctx: dict) -> list:
    """benchmarks/run.py adapter: smoke settings, CSV rows.  Writes to
    the artifacts dir so harness runs never clobber the committed
    full-mode BENCH_backbone.json with low-rep smoke numbers."""
    out = Path(__file__).resolve().parent / "artifacts"
    out.mkdir(parents=True, exist_ok=True)
    rep = run_bench(smoke=True, out=out / "BENCH_backbone.smoke.json")
    rows = []
    for r in rep["backbone"]:
        dt = r.get("dtype", "fp32")
        name = (f"bench_backbone/{r['workload']}"
                + (f"_b{r['beta']}" if r["beta"] is not None else "")
                + f"/{r['backend']}"
                + (f"/{dt}" if dt != "fp32" else ""))
        rows.append((name, r["us_per_call"], f"n_low={r['n_low']}"))
    s = rep["server_infer"]
    rows.append(("bench_backbone/server_infer_jit", s["jit_us"],
                 f"eager_us={s['eager_us']:.0f} speedup={s['speedup']:.1f}x"))
    ctx["bench_backbone"] = rows
    return rows


def main(argv=None) -> int:
    compile_cache.enable()
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true",
                    help="minimal reps/frames (CI sanity lane)")
    ap.add_argument("--backends", type=str, default=None,
                    help="comma-separated backends to bench (default: "
                         "xla,pallas on TPU; xla only elsewhere — "
                         "pallas-interpret is a slow parity path)")
    ap.add_argument("--out", type=Path, default=None,
                    help=f"output JSON path (default {DEFAULT_OUT}; "
                         "--check runs default to benchmarks/artifacts "
                         "so they never clobber the committed baseline)")
    ap.add_argument("--check", action="store_true",
                    help="compare fresh rows against the committed "
                         "BENCH_backbone.json per (workload, beta, "
                         "backend, dtype); exit 1 on a >15%% regression")
    args = ap.parse_args(argv)
    backends = (tuple(b.strip() for b in args.backends.split(","))
                if args.backends else None)
    out = args.out
    if out is None:
        if args.check:
            out = Path(__file__).resolve().parent / "artifacts" \
                / "BENCH_backbone.check.json"
            out.parent.mkdir(parents=True, exist_ok=True)
        else:
            out = DEFAULT_OUT
    rep = run_bench(smoke=args.smoke, out=out, backends=backends)
    for r in rep["backbone"]:
        beta = "-" if r["beta"] is None else r["beta"]
        print(f"  {r['workload']:>5} beta={beta} {r['backend']:>6} "
              f"{r.get('dtype', 'fp32'):>9}: "
              f"{r['us_per_call']:10.0f} us/call")
    s = rep["server_infer"]
    print(f"  server.infer jit {s['jit_us']:.0f} us vs eager "
          f"{s['eager_us']:.0f} us  ({s['speedup']:.1f}x)")
    if args.check:
        return 1 if check_regressions(rep) else 0
    return 0


if __name__ == "__main__":
    import sys
    sys.exit(main())
