#!/usr/bin/env python3
"""On-chip smoke run of the ViTDet-L serving path on one TPU.

Serves ViTDet-L at its published widths (``configs/vitdet_l.py`` CONFIG:
24 blocks, d_model 1024, 1024^2 frames) with random weights drawn from a
seed, through the entry points a deployment calls on
``offload.simulator.ServerModel``:

  1. ``warmup`` over a bounded plan space (one restoration point beta,
     reuse on, B buckets 1 and 2) with the Pallas backend — the kernel
     autotuner sweeps its block sizes on the chip first;
  2. ``infer_wave``: a full-res bootstrap of session A that captures
     restoration-point tiles; a LOW+REUSE plan for A spliced from its
     device-resident FeatureCache; a B=2 wave of two different
     (n_low, n_reuse) plans, staged with ``stage_frames`` and served with
     ``defer=True`` (the continuous scheduler's executor path); and a
     second B=2 pass that reads the caches the wave refreshed by
     donation.

It exits non-zero, printing no result line, unless JAX runs on a TPU,
every compiled executable holds a Pallas kernel (``tpu_custom_call``),
warmup left no steady-state compile, reused regions' tiles round-trip
the splice unchanged, and the Pallas lane's backbone features agree with
an XLA forward at HIGHEST matmul precision within ``REL_BOUND``.  The
wall times it prints are smoke timings, not a benchmark.

Run from the root of the repository:  python3 chip_smoke.py
The last line of its output is
{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}.
"""
from __future__ import annotations

import json
import sys
import time
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"
SEED = 0
BETA = 2                    # the restoration point the plan space uses
# Relative L2 error allowed between the served Pallas lane and the XLA
# reference.  The reference runs every matmul at HIGHEST precision; the
# served lane runs XLA's default TPU precision (one bf16 pass, unit
# roundoff 2^-9) in the projections and MLPs of all 24 blocks, so it
# differs from the reference by about a percent.  A wrong mask, window
# or splice moves whole tokens and shows up as an error of order one.
REL_BOUND = 5e-2


def fail(msg: str) -> None:
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr)
    sys.exit(1)


def rel_err(got, ref) -> float:
    import numpy as np
    got = np.asarray(got, np.float64)
    ref = np.asarray(ref, np.float64)
    return float(np.linalg.norm(got - ref) / np.linalg.norm(ref))


def plan(states_by_region):
    """RegionPlan over the 16 decision regions from {region: state}."""
    import numpy as np
    from repro.core.partition import FULL, RegionPlan
    states = np.full((16,), FULL, np.int8)
    for r, s in states_by_region.items():
        states[r] = s
    return RegionPlan(states)


def smoke(cfg, seed: int = SEED, log=print) -> None:
    """Drive the serving path once at ``cfg``; raise on a failed check."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import partition as pt
    from repro.core import vit_backbone as vb
    from repro.core.partition import LOW, REUSE
    from repro.kernels import autotune
    from repro.models import registry
    from repro.offload.simulator import ServerModel
    from repro.serve.request import FeatureCache

    def check(ok: bool, msg: str) -> None:
        if not ok:
            raise RuntimeError(msg)

    t0 = time.perf_counter()
    params = jax.block_until_ready(
        registry.init_params(cfg, jax.random.PRNGKey(seed)))
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(params))
    log(f"params: {n_params} random f32 weights (seed {seed}) in "
        f"{time.perf_counter() - t0:.1f} s")

    sm = ServerModel(cfg, params, backend="pallas")
    part = sm.part
    check(part.n_regions == 16, f"expected 16 regions, got {part.n_regions}")
    space = sm.default_plan_space(betas=(BETA,), reuse_edges=(0, 4),
                                  captures=(BETA,))
    n_exec = sm.warmup(space, batch_buckets=(1, 2))
    log(f"warmup: {n_exec} executables in {sm.stats.warmup_wall_s:.1f} s "
        f"wall (autotune sweeps included)")
    for key, sec in sorted(sm.stats.compile_s.items()):
        log(f"  compile (lb, beta, capture, B)={key}: {sec:.1f} s")
    table = (json.loads(autotune.cache_path().read_text())
             if autotune.cache_path().exists() else {})
    for kernel, buckets in sorted(table.items()):
        for bucket, entry in sorted(buckets.items()):
            log(f"  autotune {kernel} [{bucket}]: {entry['params']} "
                f"{entry['us']:.1f} us")
    # off the TPU (a CPU rehearsal) kernels run interpreted, as plain XLA
    if jax.default_backend() == "tpu":
        for key, exe in sorted(sm._fns.items()):
            check("tpu_custom_call" in exe.as_text(),
                  f"executable {key} holds no Pallas kernel")
        log(f"all {len(sm._fns)} executables hold tpu_custom_call")
    check(sm.stats.steady_compiles == 0,
          f"steady compiles after warmup: {sm.stats.steady_compile_keys}")

    H, W = cfg.vit.img_size
    rng = np.random.default_rng(seed)
    frames = rng.random((6, H, W, 3), dtype=np.float32)
    cache_a = FeatureCache(part.n_regions)
    cache_b = FeatureCache(part.n_regions)

    def timed(label, fn, *caches):
        t = time.perf_counter()
        out = fn()
        jax.block_until_ready([c.tiles for c in caches])
        log(f"request {label}: {(time.perf_counter() - t) * 1e3:.1f} ms "
            f"wall (smoke timing, not a benchmark)")
        return out

    # 1. full-res bootstrap of session A, capturing tiles at BETA
    full = plan({})
    dets = timed("full-res bootstrap A", lambda: sm.infer_wave(
        frames[:1], [full], BETA, caches=[cache_a], frame_ids=[0],
        capture_beta=BETA), cache_a)
    check(len(dets) == 1 and cache_a.warm and cache_a.beta == BETA,
          "bootstrap did not warm session A's cache")

    # 2. LOW + REUSE for A, spliced from its device-resident cache
    p_a1 = plan({0: LOW, 1: LOW, 2: LOW, 3: LOW,
                 4: REUSE, 5: REUSE, 6: REUSE, 7: REUSE})
    tiles_a0 = np.asarray(cache_a.tiles)
    timed("LOW+REUSE A (lb %d)" % sm.plan_length_bucket(p_a1),
          lambda: sm.infer_wave(frames[1:2], [p_a1], BETA, caches=[cache_a],
                                frame_ids=[1]), cache_a)
    reused = p_a1.states == REUSE
    check(np.array_equal(np.asarray(cache_a.tiles)[reused],
                         tiles_a0[reused]),
          "reused regions' tiles changed across the splice")

    # 3. staged B=2 wave of two different plans, deferred decode
    p_a2 = plan({0: LOW, 1: LOW, 2: LOW, 3: LOW, 8: LOW, 9: LOW, 10: LOW,
                 11: LOW, 4: REUSE, 5: REUSE, 6: REUSE, 7: REUSE})
    p_b1 = plan({12: LOW, 13: LOW, 14: LOW, 15: LOW})

    def staged_wave():
        staged = sm.stage_frames(frames[2:4])
        pending = sm.infer_wave(staged, [p_a2, p_b1], BETA,
                                caches=[cache_a, cache_b],
                                frame_ids=[2, 0], defer=True)
        return pending.wait()

    dets = timed("staged deferred B=2 wave", staged_wave, cache_a, cache_b)
    check(len(dets) == 2 and cache_b.warm, "B=2 wave did not serve both")

    # 4. second pass over the caches the wave refreshed by donation
    p_a3 = plan({r: REUSE for r in range(8)})
    p_b2 = plan({0: LOW, 1: LOW, 2: LOW, 3: LOW,
                 12: REUSE, 13: REUSE, 14: REUSE, 15: REUSE})
    tiles_b1 = np.asarray(cache_b.tiles)
    dets = timed("REUSE pass over donated caches", lambda: sm.infer_wave(
        frames[4:6], [p_a3, p_b2], BETA, caches=[cache_a, cache_b],
        frame_ids=[3, 1]), cache_a, cache_b)
    check(len(dets) == 2, "second pass did not serve both")
    reused = p_b2.states == REUSE
    check(np.array_equal(np.asarray(cache_b.tiles)[reused],
                         tiles_b1[reused]),
          "session B's reused tiles changed across the splice")
    for c in (cache_a, cache_b):
        check(bool(np.isfinite(np.asarray(c.tiles)).all()),
              "non-finite cached tiles")
    check(sm.stats.steady_compiles == 0,
          f"requests compiled: {sm.stats.steady_compile_keys}")
    log(f"served {sm.stats.offloads} offloads, {sm.stats.reuse_splices} "
        f"reuse splices, 0 steady-state compiles")

    # the Pallas lane (as served) against the XLA reference at HIGHEST
    def features(backend, img, beta=0, layout=None, reuse_tiles=None):
        return jax.jit(lambda p, x, lay, rt: vb.forward_features(
            cfg, p, x, beta=beta, backend=backend, layout=lay,
            reuse_tiles=rt))(params, img, layout, reuse_tiles)

    lb = sm.plan_length_bucket(p_a1)
    layout = pt.plan_layout(p_a1.states, lb, part)
    arrays, _ = pt.stack_plan_layouts([layout])
    ids = np.where(layout.reuse_ids < part.n_regions, layout.reuse_ids, 0)
    mixed = {"beta": BETA, "reuse_tiles": jnp.asarray(tiles_a0[ids][None]),
             "layout": {k: jnp.asarray(v) for k, v in arrays.items()}}
    for label, img, kw in (("full-res", frames[:1], {}),
                           (f"mixed lb={lb}", frames[1:2], mixed)):
        got = features("pallas", img, **kw)
        with jax.default_matmul_precision("highest"):
            ref = features("xla", img, **kw)
        err = rel_err(got, ref)
        log(f"features {label}: relative L2 error vs XLA HIGHEST "
            f"{err:.3e} (bound {REL_BOUND:.0e})")
        check(bool(np.isfinite(np.asarray(got)).all()),
              f"non-finite {label} features")
        check(err <= REL_BOUND, f"{label} features off the reference")

    stats = jax.devices()[0].memory_stats() or {}
    log(f"peak_bytes_in_use: {stats.get('peak_bytes_in_use', 'n/a')}")


def main() -> int:
    if not (SRC / "repro").is_dir():
        fail(f"the program is not here: no {SRC / 'repro'} next to "
             f"{Path(__file__).name}")
    sys.path.insert(0, str(SRC))
    from repro import compile_cache
    cache_dir = compile_cache.enable()

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        fail(f"no TPU: JAX found only {devices[0].platform!r} devices; "
             f"this smoke run needs a TPU chip")
    dev = devices[0]
    print(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; "
          f"compile cache {cache_dir}")
    from repro.configs.vitdet_l import CONFIG
    t0 = time.perf_counter()
    smoke(CONFIG)
    print(f"smoke wall: {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
