"""Zero-stall serving benchmark — AOT warmup over the COLLAPSED
(length bucket, beta, capture, B bucket) executable grid,
device-resident feature caches, cross-bucket wave coalescing.

Emits ``BENCH_serving.json`` with three sections:

  * ``warmup``   — first-offload wall latency and p95 per-offload server
                   wall time for a lazy-compile replica vs. an
                   AOT-warmed one, plus the COMPILE SURFACE: total
                   executables, the warmed key list, warmup wall time
                   (gated by ``--max-warmup-s``), and steady-state
                   compiles (MUST be 0 after warmup — the bench fails
                   under ``--check`` otherwise, as does a surface
                   larger than ``EXEC_BUDGET`` executables);
  * ``cache``    — host<->device tile bytes per offload on a reuse-heavy
                   parkS workload, device-resident FeatureCache vs. the
                   legacy host-resident mode (device mode MUST be 0);
  * ``coalesce`` — mean wave size, mixed-plan wave counts (waves
                   batching >= 2 distinct n_low values in ONE
                   executable), throughput and p95 e2e (queueing
                   included) on a multi-length-bucket workload with and
                   without cross-bucket coalescing, plus rendering-F1
                   deltas on the parkS/driveN scenarios (promotion only
                   ever PADS the sequence, so the deltas must be 0.000);
  * ``speculation`` — speculative REUSE execution on a slow uplink
                   (stacked bufferbloat overlay): continuous vs.
                   continuous+speculative on a 4-client parkS/driveN
                   workload — speculation MUST cut p50 e2e, keep the
                   rendering-F1 delta within 0.005 per scenario, add
                   ZERO executable keys and steady compiles, launch and
                   patch at least once, and a zero-tolerance probe MUST
                   exercise the discard-and-rerun path;
  * ``scheduling`` — barrier vs. continuous wave scheduling
                   (``EdgeConfig(scheduler=...)``) on a contended
                   4-client workload against ONE pre-warmed replica:
                   p50 queue delay and ``device_idle_frac`` (continuous
                   must beat barrier on both — the decode/h2d overlap
                   win), zero steady-state compiles and ZERO new
                   executable keys for the continuous run (it must
                   reuse the warmed grid), and a 0.000 rendering-F1
                   delta (scheduling moves timestamps, never boxes).

Standalone:  python benchmarks/bench_serving.py [--smoke] [--check]
                    [--max-warmup-s S]
Harness:     picked up by benchmarks/run.py as the ``bench_serving``
             suite (smoke settings, check enabled).
"""
from __future__ import annotations

import argparse
import json
import time
from pathlib import Path
from typing import Dict, List, Optional

import jax
import numpy as np

from repro import compile_cache
from repro.configs.vitdet_l import SIM
from repro.core import partition as pt
from repro.core import vit_backbone as vb
from repro.core.partition import REUSE, RegionPlan
from repro.data import synthetic_video as sv
from repro.data.network_traces import make_trace
from repro.models import registry
from repro.offload.estimator import InferenceDelayModel
from repro.offload.faults import FaultInjector, FaultSpec, FaultyTrace
from repro.offload.optimizer import build_reuse_plan
from repro.offload.simulator import Policy, ServerModel, Simulation
from repro.serve.edge import (BatchedServerModel, EdgeConfig,
                              MultiClientSimulation)
from repro.serve.request import FeatureCache

DEFAULT_OUT = Path(__file__).resolve().parent.parent / "BENCH_serving.json"
PATCH = SIM.vit.patch_size
SIZE = SIM.vit.img_size[0]
FPS = 10
FULL_RES_DELAY_S = 0.281
BETA = 2
REUSE_K = 4
# the collapsed grid must stay within this many executables (vs 56 on
# the old (n_low bucket, n_reuse bucket, beta, capture, B) grid)
EXEC_BUDGET = 16


def _params():
    return registry.init_params(SIM, jax.random.PRNGKey(0))


def _inf_delay_model() -> InferenceDelayModel:
    part = vb.vit_partition(SIM)
    # LM^inf costs the PADDED length bucket the replica actually runs,
    # not the exact mixed length — the executable grid pads sequences
    # up to pt.length_bucket_set edges
    edges = pt.length_bucket_set(part)
    return InferenceDelayModel.fit_from_flops(
        lambda n, b, r=0: vb.backbone_flops(SIM, n, b, r,
                                            length_edges=edges),
        part.n_regions,
        betas=tuple(range(SIM.vit.n_subsets + 1)),
        full_res_delay_s=FULL_RES_DELAY_S)


def _mask(part, lows) -> np.ndarray:
    m = np.zeros(part.n_regions, np.int32)
    m[list(lows)] = 1
    return m


# ---------------------------------------------------------------------------
# section 1: AOT warmup vs. lazy compile


def _serving_trace(server: ServerModel, frames: np.ndarray,
                   with_reuse: bool) -> List[float]:
    """A representative steady-state serving trace (solo calls + waves
    across the plan space); returns per-call wall seconds."""
    part = server.part
    plan4 = RegionPlan.from_mask(_mask(part, range(4)))
    plan8 = RegionPlan.from_mask(_mask(part, range(8)))
    cache = FeatureCache(part.n_regions, max_age=REUSE_K)
    states = plan4.states.copy()
    states[8:12] = REUSE
    plan_r = RegionPlan(states)
    walls = []

    def call(fn, *a, **kw):
        t0 = time.perf_counter()
        fn(*a, **kw)
        walls.append(time.perf_counter() - t0)

    n = len(frames)
    for i in range(n):
        f = frames[i % n]
        call(server.infer, f)                              # full-res solo
        call(server.infer, f, _mask(part, range(4)), BETA)  # mixed solo
        call(server.infer_wave, frames[:2], [plan4, plan4], BETA)
        call(server.infer_wave, frames[:3], [plan8] * 3, BETA)
        if with_reuse:
            if not cache.warm:
                call(server.infer_plan, f, plan4, BETA, cache, i)
            else:
                call(server.infer_plan, f, plan_r, BETA, cache, i)
    return walls


def bench_warmup(n_frames: int) -> Dict:
    frames, _ = sv.make_clip("walkS", max(n_frames, 4), size=SIZE, seed=3)
    frames = frames[:max(n_frames, 4)]
    rows = {}
    for mode in ("lazy", "warmed"):
        server = ServerModel(SIM, _params(), top_k=8, score_thresh=0.0)
        warm_wall = 0.0
        if mode == "warmed":
            # captures include BETA: reuse sessions capture tiles at the
            # restoration point even on their (n_reuse = 0) warm-up
            # offloads, so those executables are part of the grid
            space = server.default_plan_space(
                betas=(BETA,), reuse_edges=(0, 4), captures=(0, BETA))
            server.warmup(space)
            warm_wall = server.stats.warmup_wall_s
        walls = _serving_trace(server, frames, with_reuse=True)
        rows[mode] = {
            "first_offload_wall_s": walls[0],
            "p50_offload_wall_s": float(np.percentile(walls, 50)),
            "p95_offload_wall_s": float(np.percentile(walls, 95)),
            "warmup_wall_s": warm_wall,
            "executables_total": server.stats.compiles,
            "compile_surface": sorted([list(k) for k in server._fns]),
            "steady_compiles": server.stats.steady_compiles,
            "steady_compile_keys": [list(k) for k in
                                    server.stats.steady_compile_keys],
        }
    rows["first_offload_speedup"] = (
        rows["lazy"]["first_offload_wall_s"]
        / max(rows["warmed"]["first_offload_wall_s"], 1e-12))
    return rows


# ---------------------------------------------------------------------------
# section 2: device-resident FeatureCache


class FixedReusePolicy(Policy):
    """Static low mask + motion-gated reuse at a fixed restoration point
    (deterministic; exercises the full plan/cache plumbing)."""
    name = "fixed-reuse"
    use_tracker = True
    reuse_k = REUSE_K

    def __init__(self, n_regions, lows=(0, 1, 2, 3), beta=BETA):
        self.n_regions = n_regions
        self.lows = list(lows)
        self.beta = beta

    def decide(self, sim, frame_idx):
        mask = np.zeros(self.n_regions, np.int32)
        mask[self.lows] = 1
        cache = sim.feature_cache
        elig = (cache.eligible(self.beta) if cache is not None
                else np.zeros(self.n_regions, bool))
        plan = build_reuse_plan(sim.part, mask, sim.m, elig)
        return {"mask": mask, "quality": 85, "beta": self.beta,
                "plan": plan, "capture_beta": self.beta}


def bench_cache(n_frames: int) -> Dict:
    part = vb.vit_partition(SIM)
    frames, _ = sv.make_clip("parkS", n_frames, size=SIZE, seed=23)
    rows = {}
    for mode, device in (("device", True), ("host", False)):
        server = ServerModel(SIM, _params(), top_k=8, score_thresh=0.0,
                             device_cache=device)
        gt = [server.infer(f) for f in frames]
        n_offloads0 = server.stats.offloads
        b0 = server.stats.tile_bytes
        sim = Simulation(frames, gt, make_trace("4g", 0, duration_s=120),
                         FixedReusePolicy(part.n_regions), server, part,
                         PATCH, fps=FPS, inf_delay=_inf_delay_model())
        sim.run("parkS")
        offloads = server.stats.offloads - n_offloads0
        reused = int((sim.feature_cache.age > 0).sum())
        rows[mode] = {
            "offloads": offloads,
            "tile_bytes_h2d": server.stats.tile_bytes_h2d,
            "tile_bytes_d2h": server.stats.tile_bytes_d2h,
            "tile_bytes_per_offload": (server.stats.tile_bytes - b0)
            / max(offloads, 1),
            "cache_on_device": bool(sim.feature_cache.tiles_on_device),
            "regions_reused_at_end": reused,
        }
    return rows


# ---------------------------------------------------------------------------
# section 3: cross-bucket wave coalescing


class FixedMaskPolicy(Policy):
    name = "fixedmask"
    use_tracker = True

    def __init__(self, lows, n_regions, beta=BETA):
        self.lows = list(lows)
        self.n_regions = n_regions
        self.beta = beta

    def decide(self, sim, frame_idx):
        m = np.zeros(self.n_regions, np.int32)
        m[self.lows] = 1
        return {"mask": m, "quality": 85, "beta": self.beta}


def _bucket_clients(server, part, video_specs, n_frames, gt_cache):
    inf_delay = _inf_delay_model()
    clients = []
    for i, (video, lows) in enumerate(video_specs):
        key = (video, n_frames)
        if key not in gt_cache:
            frames, _ = sv.make_clip(video, n_frames, size=SIZE, seed=23)
            gt_cache[key] = (frames, [server.infer(f) for f in frames])
        frames, gt = gt_cache[key]
        clients.append(Simulation(
            frames, gt, make_trace("4g", i, duration_s=240),
            FixedMaskPolicy(lows, part.n_regions), server, part, PATCH,
            fps=FPS, inf_delay=inf_delay))
    return clients


def _run_coalesce(server, part, video_specs, n_frames, coalesce,
                  gt_cache, keep=None) -> Dict:
    clients = _bucket_clients(server, part, video_specs, n_frames,
                              gt_cache)
    mc = MultiClientSimulation(clients, server,
                               EdgeConfig(batched=True,
                                          coalesce=coalesce),
                               on_complete=keep)
    results = mc.run([v for v, _ in video_specs])
    e2e = np.array([x for r in results for x in r.e2e_latency], np.float64)
    rf1 = {}
    for r in results:
        rf1.setdefault(r.video, []).extend(r.rendering_f1)
    return {
        "coalesce": coalesce,
        "offloads": int(e2e.size),
        "throughput_fps": float(e2e.size / (n_frames / FPS)),
        "p50_e2e_s": float(np.percentile(e2e, 50)) if e2e.size else None,
        "p95_e2e_s": float(np.percentile(e2e, 95)) if e2e.size else None,
        "mean_wave": mc.stats.mean_wave_size,
        "promoted_jobs": mc.stats.promoted,
        # waves that batched >= 2 distinct n_low values in ONE
        # executable — the collapsed-grid win coalescing builds on
        "mixed_plan_waves": mc.stats.mixed_plan_waves,
        "max_distinct_n_low_per_wave": (max(mc.stats.wave_n_low_mix)
                                        if mc.stats.wave_n_low_mix
                                        else 0),
        "median_rendering_f1": {v: float(np.median(x))
                                for v, x in rf1.items()},
    }


def bench_coalesce(n_frames: int) -> Dict:
    from repro.offload import detection as det
    part = vb.vit_partition(SIM)
    server = BatchedServerModel(SIM, _params(), top_k=8, score_thresh=0.0)
    gt_cache: Dict = {}

    # (a) multi-length-bucket workload: the clients span three length
    # buckets (n_low 4 -> 64 windows, 8/12 -> 48, 16 -> 24 at SIM
    # scale), so same-bucket jobs already co-batch WITHOUT coalescing
    # (the collapsed grid batches any n_low mix at one bucket); wave
    # growth beyond that is cross-bucket promotion — padding a shorter
    # job up to the wave's bucket.  For each promoted job we also quote
    # the inference-F1 cost of the promotion itself: F1(promoted dets)
    # - F1(the dets a solo run at the job's OWN bucket yields), timeline
    # effects excluded (promotion only pads, so this must be ~0).
    specs = [("parkS", range(4)), ("parkS", range(12)),
             ("driveN", range(8)), ("driveN", range(16))]
    promoted_jobs: List[Dict] = []

    def keep(ci, job):
        if "promoted_lb" in job:
            promoted_jobs.append({"video": specs[ci][0], **job})

    on = _run_coalesce(server, part, specs, n_frames, True, gt_cache,
                       keep=keep)
    off = _run_coalesce(server, part, specs, n_frames, False, gt_cache)

    f1_cost = []
    for job in promoted_jobs:
        gt = gt_cache[(job["video"], n_frames)][1][job["frame"]]
        own = server.infer_wave(job["decoded"][None], [job["plan"]],
                                job["beta"])[0]
        f1_cost.append(det.frame_f1(job["dets"], gt)
                       - det.frame_f1(own, gt))

    # (b) the EXISTING parkS/driveN scenarios (same-bucket clients, the
    # bench_reuse workload shape): enabling coalescing must be a perfect
    # no-op there — no cross-bucket jobs exist, so the scheduler, the
    # timeline, and the rendering F1 must be IDENTICAL (delta 0.000).
    deltas = {}
    for video in ("parkS", "driveN"):
        sp = [(video, range(4)), (video, range(4))]
        s_on = _run_coalesce(server, part, sp, n_frames, True, gt_cache)
        s_off = _run_coalesce(server, part, sp, n_frames, False, gt_cache)
        assert s_on["promoted_jobs"] == 0
        deltas[video] = (s_on["median_rendering_f1"][video]
                         - s_off["median_rendering_f1"][video])

    return {"on": on, "off": off,
            "promotion_inference_f1_delta": {
                "n": len(f1_cost),
                "mean": float(np.mean(f1_cost)) if f1_cost else 0.0,
                "median": float(np.median(f1_cost)) if f1_cost else 0.0,
            },
            "rendering_f1_delta": deltas}


# ---------------------------------------------------------------------------
# section 4: barrier vs. continuous wave scheduling


def _run_sched(server, part, video_specs, n_frames, scheduler,
               gt_cache) -> Dict:
    clients = _bucket_clients(server, part, video_specs, n_frames,
                              gt_cache)
    mc = MultiClientSimulation(clients, server,
                               EdgeConfig(batched=True,
                                          scheduler=scheduler))
    results = mc.run([v for v, _ in video_specs])
    e2e = np.array([x for r in results for x in r.e2e_latency], np.float64)
    queue = np.asarray(mc.stats.queue_delays, np.float64)
    admit = np.asarray(mc.stats.queue_admit, np.float64)
    slot = np.asarray(mc.stats.queue_slot, np.float64)
    rf1 = {}
    for r in results:
        rf1.setdefault(r.video, []).extend(r.rendering_f1)

    def p(x, q):
        return float(np.percentile(x, q)) if x.size else 0.0

    return {
        "scheduler": scheduler,
        "offloads": int(e2e.size),
        "p50_e2e_s": p(e2e, 50),
        "p95_e2e_s": p(e2e, 95),
        "p50_queue_s": p(queue, 50),
        "p95_queue_s": p(queue, 95),
        "p50_queue_admit_s": p(admit, 50),
        "p50_queue_slot_s": p(slot, 50),
        "device_idle_frac": mc.stats.device_idle_frac,
        "decode_hidden_s": mc.stats.decode_hidden_s,
        "mean_wave": mc.stats.mean_wave_size,
        "median_rendering_f1": {v: float(np.median(x))
                                for v, x in rf1.items()},
    }


def bench_scheduling(n_frames: int) -> Dict:
    part = vb.vit_partition(SIM)
    server = BatchedServerModel(SIM, _params(), top_k=8, score_thresh=0.0)
    gt_cache: Dict = {}
    # 4 same-bucket clients on a static scene: the shared replica is a
    # genuine bottleneck (FULL_RES_DELAY_S service model at 10 FPS), so
    # barrier waves idle the device through every decode window and
    # queue arrivals behind it; continuous stages decode/h2d under the
    # running wave.  parkS is static, so the timing shift cannot move
    # which boxes a frame renders — the F1 delta gate is exact.
    specs = [("parkS", range(4))] * 4
    # ground truth BEFORE warmup (full-res solo inference), then warm
    # the grid the workload needs — every compile after this point is a
    # steady-state stall, and the continuous run must add ZERO keys
    for video, _ in specs:
        key = (video, n_frames)
        if key not in gt_cache:
            frames, _ = sv.make_clip(video, n_frames, size=SIZE, seed=23)
            gt_cache[key] = (frames, [server.infer(f) for f in frames])
    server.warmup(server.default_plan_space(betas=(BETA,)))

    barrier = _run_sched(server, part, specs, n_frames, "barrier",
                         gt_cache)
    keys0, compiles0 = set(server._fns), server.stats.compiles
    cont = _run_sched(server, part, specs, n_frames, "continuous",
                      gt_cache)
    new_keys = sorted(list(k) for k in set(server._fns) - keys0)
    f1_delta = {v: cont["median_rendering_f1"][v]
                - barrier["median_rendering_f1"][v]
                for v in barrier["median_rendering_f1"]}
    return {"barrier": barrier, "continuous": cont,
            "steady_compiles": server.stats.steady_compiles,
            "continuous_new_executables": new_keys,
            "continuous_new_compiles": server.stats.compiles - compiles0,
            "rendering_f1_delta": f1_delta}


# ---------------------------------------------------------------------------
# section 5: speculative REUSE execution on a slow uplink


# The congested-cell overlay: bufferbloat windows from offload/faults.py
# COMPOUND when stacked (each dents throughput to 70 % and inflates RTT
# by its factor), so ten whole-run windows leave ~3 % of the uplink
# (0.7^10) at ~4x RTT (1.15^10) — the regime where transmission, not
# inference, dominates Eq. (2) and speculation has an uplink to hide in.
SLOW_UPLINK = FaultSpec(
    bufferbloat=tuple((0.0, 3600.0, 1.15) for _ in range(10)))


def _reuse_clients(server, part, video_specs, n_frames, gt_cache):
    inf_delay = _inf_delay_model()
    clients = []
    for i, (video, lows) in enumerate(video_specs):
        key = (video, n_frames)
        if key not in gt_cache:
            frames, _ = sv.make_clip(video, n_frames, size=SIZE, seed=23)
            gt_cache[key] = (frames, [server.infer(f) for f in frames])
        frames, gt = gt_cache[key]
        trace = FaultyTrace(make_trace("4g", i, duration_s=240),
                            FaultInjector(SLOW_UPLINK))
        clients.append(Simulation(
            frames, gt, trace,
            FixedReusePolicy(part.n_regions, lows=lows), server, part,
            PATCH, fps=FPS, inf_delay=inf_delay))
    return clients


def _run_spec(server, part, video_specs, n_frames, gt_cache,
              speculate, **spec_kw) -> Dict:
    clients = _reuse_clients(server, part, video_specs, n_frames,
                             gt_cache)
    mc = MultiClientSimulation(clients, server,
                               EdgeConfig(batched=True,
                                          scheduler="continuous",
                                          speculate=speculate, **spec_kw))
    results = mc.run([v for v, _ in video_specs])
    e2e = np.array([x for r in results for x in r.e2e_latency], np.float64)
    rf1 = {}
    for r in results:
        rf1.setdefault(r.video, []).extend(r.rendering_f1)

    def p(x, q):
        return float(np.percentile(x, q)) if x.size else 0.0

    return {
        "speculate": speculate,
        "offloads": int(e2e.size),
        "p50_e2e_s": p(e2e, 50),
        "p95_e2e_s": p(e2e, 95),
        "p50_queue_s": p(np.asarray(mc.stats.queue_delays), 50),
        "device_idle_frac": mc.stats.device_idle_frac,
        "spec_launched": mc.stats.spec_launched,
        "spec_patched": mc.stats.spec_patched,
        "spec_discarded": mc.stats.spec_discarded,
        "spec_hidden_s": mc.stats.spec_hidden_s,
        "p50_spec_hidden_s": mc.stats.spec_hidden_percentile(50),
        "p95_spec_hidden_s": mc.stats.spec_hidden_percentile(95),
        "median_rendering_f1": {v: float(np.median(x))
                                for v, x in rf1.items()},
    }


def bench_speculation(n_frames: int) -> Dict:
    part = vb.vit_partition(SIM)
    server = BatchedServerModel(SIM, _params(), top_k=8, score_thresh=0.0)
    gt_cache: Dict = {}
    # slow-uplink 4-client workload: three reuse-heavy parkS sessions
    # (static scene -> high REUSE fraction + high prediction confidence
    # -> speculation admits and converges) and one driveN session (real
    # motion -> low confidence, the lane mostly stands down).  Ground
    # truth before warmup, then warm the grid — speculation must add
    # ZERO keys on top of it.
    specs = [("parkS", range(4)), ("parkS", range(4, 8)),
             ("parkS", range(8, 12)), ("driveN", range(4))]
    for video, _ in specs:
        key = (video, n_frames)
        if key not in gt_cache:
            frames, _ = sv.make_clip(video, n_frames, size=SIZE, seed=23)
            gt_cache[key] = (frames, [server.infer(f) for f in frames])
    server.warmup(server.default_plan_space(
        betas=(BETA,), reuse_edges=(0, 4), captures=(0, BETA)))

    cont = _run_spec(server, part, specs, n_frames, gt_cache, False)
    keys0, compiles0 = set(server._fns), server.stats.compiles
    spec = _run_spec(server, part, specs, n_frames, gt_cache, True)
    new_keys = sorted(list(k) for k in set(server._fns) - keys0)
    # discard probe: zero tolerance + zero patch budget turns every
    # resolved speculation into a discard-and-rerun (codec noise always
    # diverges a transmitted region at tol=0), pinning the discard path
    # end to end on the real executor
    probe = _run_spec(server, part, specs[:1], n_frames, gt_cache, True,
                      spec_patch_tol=0.0, spec_max_patch_frac=0.0)
    f1_delta = {v: spec["median_rendering_f1"][v]
                - cont["median_rendering_f1"][v]
                for v in cont["median_rendering_f1"]}
    return {"continuous": cont, "speculative": spec,
            "discard_probe": probe,
            "steady_compiles": server.stats.steady_compiles,
            "speculation_new_executables": new_keys,
            "speculation_new_compiles": server.stats.compiles - compiles0,
            "rendering_f1_delta": f1_delta}


# ---------------------------------------------------------------------------


def check(report: Dict,
          max_warmup_s: Optional[float] = None) -> List[str]:
    """The acceptance gates ci.sh enforces on the smoke lane."""
    errs = []
    w = report["warmup"]
    if w["warmed"]["steady_compiles"] != 0:
        errs.append(f"steady-state compiles after warmup: "
                    f"{w['warmed']['steady_compiles']} "
                    f"{w['warmed']['steady_compile_keys']}")
    if not (w["warmed"]["first_offload_wall_s"]
            < w["lazy"]["first_offload_wall_s"]):
        errs.append("warmup did not reduce first-offload latency")
    # the collapsed compile surface: length buckets, not (n_low, n_reuse)
    if w["warmed"]["executables_total"] > EXEC_BUDGET:
        errs.append(f"compile surface regressed: "
                    f"{w['warmed']['executables_total']} executables "
                    f"> budget {EXEC_BUDGET}")
    if max_warmup_s is not None and \
            w["warmed"]["warmup_wall_s"] > max_warmup_s:
        errs.append(f"warmup wall time {w['warmed']['warmup_wall_s']:.1f}s"
                    f" > budget {max_warmup_s:.1f}s")
    if report["cache"]["device"]["tile_bytes_per_offload"] != 0:
        errs.append("device-resident cache shipped tile bytes")
    if report["cache"]["host"]["tile_bytes_per_offload"] <= 0:
        errs.append("host-resident baseline counted no tile bytes")
    c = report["coalesce"]
    if not c["on"]["mean_wave"] > c["off"]["mean_wave"]:
        errs.append(f"coalescing did not grow waves: "
                    f"{c['on']['mean_wave']} <= {c['off']['mean_wave']}")
    if c["on"]["promoted_jobs"] <= 0:
        errs.append("no jobs were promoted")
    if c["off"]["mixed_plan_waves"] + c["on"]["mixed_plan_waves"] <= 0:
        errs.append("no wave batched plans with distinct n_low values "
                    "in one executable")
    # promotion must not cost inference accuracy: F1(promoted dets) >=
    # F1(own-bucket dets) on average (promotion only PADS the sequence)
    if c["promotion_inference_f1_delta"]["mean"] < 0:
        errs.append(f"promotion degraded inference F1: "
                    f"{c['promotion_inference_f1_delta']}")
    for v, d in c["rendering_f1_delta"].items():
        if abs(d) > 1e-12:
            errs.append(f"rendering-F1 delta on {v}: {d:+.4f}")
    s = report["scheduling"]
    if not (s["continuous"]["p50_queue_s"]
            < s["barrier"]["p50_queue_s"]):
        errs.append(f"continuous did not cut p50 queue delay: "
                    f"{s['continuous']['p50_queue_s']:.3f}s >= "
                    f"{s['barrier']['p50_queue_s']:.3f}s")
    if not (s["continuous"]["device_idle_frac"]
            < s["barrier"]["device_idle_frac"]):
        errs.append(f"continuous did not cut device idle: "
                    f"{s['continuous']['device_idle_frac']:.3f} >= "
                    f"{s['barrier']['device_idle_frac']:.3f}")
    if s["steady_compiles"] != 0:
        errs.append(f"scheduling workload compiled in steady state: "
                    f"{s['steady_compiles']}")
    if s["continuous_new_executables"] or s["continuous_new_compiles"]:
        errs.append(f"continuous scheduling grew the executable grid: "
                    f"+{s['continuous_new_compiles']} compiles "
                    f"{s['continuous_new_executables']}")
    for v, d in s["rendering_f1_delta"].items():
        if abs(d) > 1e-12:
            errs.append(f"scheduler rendering-F1 delta on {v}: {d:+.4f}")
    sp = report["speculation"]
    if not (sp["speculative"]["p50_e2e_s"]
            < sp["continuous"]["p50_e2e_s"]):
        errs.append(f"speculation did not cut p50 e2e on the slow "
                    f"uplink: {sp['speculative']['p50_e2e_s']:.3f}s >= "
                    f"{sp['continuous']['p50_e2e_s']:.3f}s")
    if sp["speculative"]["spec_launched"] <= 0 \
            or sp["speculative"]["spec_patched"] <= 0:
        errs.append(f"speculation lane idle: launched "
                    f"{sp['speculative']['spec_launched']} patched "
                    f"{sp['speculative']['spec_patched']}")
    if sp["discard_probe"]["spec_discarded"] < 1:
        errs.append("discard path never exercised (zero-tolerance probe "
                    "produced no discards)")
    for v, d in sp["rendering_f1_delta"].items():
        if abs(d) > 0.005:
            errs.append(f"speculation rendering-F1 delta on {v}: "
                        f"{d:+.4f} (budget 0.005)")
    if sp["steady_compiles"] != 0:
        errs.append(f"speculation workload compiled in steady state: "
                    f"{sp['steady_compiles']}")
    if sp["speculation_new_executables"] or sp["speculation_new_compiles"]:
        errs.append(f"speculation grew the executable grid: "
                    f"+{sp['speculation_new_compiles']} compiles "
                    f"{sp['speculation_new_executables']}")
    return errs


def run_bench(smoke: bool = False, out: Path = DEFAULT_OUT,
              do_check: bool = False,
              max_warmup_s: Optional[float] = None) -> dict:
    n_frames = 16 if smoke else 40
    part = vb.vit_partition(SIM)
    report = {
        "meta": {
            "config": "vitdet-l/SIM",
            "device": jax.default_backend(),
            "smoke": smoke,
            "n_frames": n_frames,
            "fps": FPS,
            "beta": BETA,
            "reuse_k": REUSE_K,
            "full_res_delay_s": FULL_RES_DELAY_S,
            "batch_buckets": list(pt.BATCH_BUCKETS),
            "length_bucket_edges": list(pt.length_bucket_set(part)),
            "exec_budget": EXEC_BUDGET,
            "max_warmup_s": max_warmup_s,
        },
        "warmup": bench_warmup(4 if smoke else 8),
        "cache": bench_cache(n_frames),
        "coalesce": bench_coalesce(n_frames),
        "scheduling": bench_scheduling(n_frames),
        # the slow uplink needs enough sim time for bootstrap + several
        # speculative offloads per client even in smoke
        "speculation": bench_speculation(max(n_frames, 40)),
    }
    errs = check(report, max_warmup_s=max_warmup_s)
    report["check"] = {"passed": not errs, "errors": errs}
    out.write_text(json.dumps(report, indent=2) + "\n")
    print(f"[bench_serving] wrote {out}")
    if do_check and errs:
        raise SystemExit("[bench_serving] CHECK FAILED: " + "; ".join(errs))
    return report


def run(ctx: dict) -> list:
    """benchmarks/run.py adapter: smoke settings, CSV rows."""
    out = Path(__file__).resolve().parent / "artifacts"
    out.mkdir(parents=True, exist_ok=True)
    rep = run_bench(smoke=True, out=out / "BENCH_serving.smoke.json",
                    do_check=True, max_warmup_s=90.0)
    w, c = rep["warmup"], rep["coalesce"]
    rows = [
        ("bench_serving/first_offload/lazy",
         w["lazy"]["first_offload_wall_s"] * 1e6,
         f"execs={w['lazy']['executables_total']}"),
        ("bench_serving/first_offload/warmed",
         w["warmed"]["first_offload_wall_s"] * 1e6,
         f"execs={w['warmed']['executables_total']} "
         f"steady_compiles={w['warmed']['steady_compiles']}"),
        ("bench_serving/warmup_wall", w["warmed"]["warmup_wall_s"] * 1e6,
         f"execs={w['warmed']['executables_total']} "
         f"budget={EXEC_BUDGET}"),
        ("bench_serving/tile_bytes/device", 0.0,
         f"per_offload={rep['cache']['device']['tile_bytes_per_offload']:.0f}"),
        ("bench_serving/tile_bytes/host", 0.0,
         f"per_offload={rep['cache']['host']['tile_bytes_per_offload']:.0f}"),
        ("bench_serving/coalesce", 0.0,
         f"wave {c['off']['mean_wave']:.2f}->{c['on']['mean_wave']:.2f} "
         f"promoted={c['on']['promoted_jobs']} "
         f"mixed_waves={c['on']['mixed_plan_waves']}"),
        ("bench_serving/scheduling",
         rep["scheduling"]["continuous"]["p50_queue_s"] * 1e6,
         f"queue p50 "
         f"{rep['scheduling']['barrier']['p50_queue_s']:.3f}s->"
         f"{rep['scheduling']['continuous']['p50_queue_s']:.3f}s "
         f"idle {rep['scheduling']['barrier']['device_idle_frac']:.2f}->"
         f"{rep['scheduling']['continuous']['device_idle_frac']:.2f}"),
        ("bench_serving/speculation",
         rep["speculation"]["speculative"]["p50_e2e_s"] * 1e6,
         f"e2e p50 {rep['speculation']['continuous']['p50_e2e_s']:.3f}s"
         f"->{rep['speculation']['speculative']['p50_e2e_s']:.3f}s "
         f"launched={rep['speculation']['speculative']['spec_launched']} "
         f"patched={rep['speculation']['speculative']['spec_patched']} "
         f"hidden p50="
         f"{rep['speculation']['speculative']['p50_spec_hidden_s']:.3f}s"),
    ]
    ctx["bench_serving"] = rows
    return rows


def main(argv=None) -> int:
    compile_cache.enable()
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true",
                    help="fewer frames (CI sanity lane)")
    ap.add_argument("--check", action="store_true",
                    help="exit nonzero unless all acceptance gates hold")
    ap.add_argument("--max-warmup-s", type=float, default=None,
                    help="fail --check when the warmed replica's warmup "
                         "wall time exceeds this budget (seconds)")
    ap.add_argument("--out", type=Path, default=DEFAULT_OUT,
                    help=f"output JSON path (default {DEFAULT_OUT})")
    args = ap.parse_args(argv)
    rep = run_bench(smoke=args.smoke, out=args.out, do_check=args.check,
                    max_warmup_s=args.max_warmup_s)
    w = rep["warmup"]
    print(f"  first offload: lazy {w['lazy']['first_offload_wall_s']:.3f}s"
          f" -> warmed {w['warmed']['first_offload_wall_s']:.3f}s "
          f"({w['first_offload_speedup']:.1f}x); "
          f"p95 {w['lazy']['p95_offload_wall_s']:.3f}s -> "
          f"{w['warmed']['p95_offload_wall_s']:.3f}s")
    print(f"  executables: lazy {w['lazy']['executables_total']} "
          f"(all steady) vs warmed {w['warmed']['executables_total']} "
          f"(budget {EXEC_BUDGET}, steady "
          f"{w['warmed']['steady_compiles']}, warmup "
          f"{w['warmed']['warmup_wall_s']:.1f}s)")
    for mode in ("device", "host"):
        r = rep["cache"][mode]
        print(f"  tiles/{mode}: {r['tile_bytes_per_offload']:.0f} B/offload"
              f" (h2d {r['tile_bytes_h2d']}, d2h {r['tile_bytes_d2h']}, "
              f"{r['offloads']} offloads)")
    c = rep["coalesce"]
    print(f"  coalesce: wave {c['off']['mean_wave']:.2f} -> "
          f"{c['on']['mean_wave']:.2f}, promoted "
          f"{c['on']['promoted_jobs']}, mixed-plan waves "
          f"{c['off']['mixed_plan_waves']}->{c['on']['mixed_plan_waves']},"
          f" p95 e2e "
          f"{c['off']['p95_e2e_s']:.3f}s -> {c['on']['p95_e2e_s']:.3f}s")
    print(f"  promotion inference-F1 cost: "
          f"{c['promotion_inference_f1_delta']}; scenario rendering-F1 "
          f"deltas {c['rendering_f1_delta']}")
    s = rep["scheduling"]
    print(f"  scheduling: queue p50 {s['barrier']['p50_queue_s']:.3f}s "
          f"(barrier) -> {s['continuous']['p50_queue_s']:.3f}s "
          f"(continuous), idle {s['barrier']['device_idle_frac']:.3f} -> "
          f"{s['continuous']['device_idle_frac']:.3f}, decode hidden "
          f"{s['continuous']['decode_hidden_s']:.2f}s, new execs "
          f"{s['continuous_new_compiles']}, F1 deltas "
          f"{s['rendering_f1_delta']}")
    sp = rep["speculation"]
    print(f"  speculation: e2e p50 {sp['continuous']['p50_e2e_s']:.3f}s "
          f"(continuous) -> {sp['speculative']['p50_e2e_s']:.3f}s "
          f"(speculative), launched "
          f"{sp['speculative']['spec_launched']}, patched "
          f"{sp['speculative']['spec_patched']}, discarded "
          f"{sp['speculative']['spec_discarded']} (+probe "
          f"{sp['discard_probe']['spec_discarded']}), hidden p50/p95 "
          f"{sp['speculative']['p50_spec_hidden_s']:.3f}/"
          f"{sp['speculative']['p95_spec_hidden_s']:.3f}s, new execs "
          f"{sp['speculation_new_compiles']}, F1 deltas "
          f"{sp['rendering_f1_delta']}")
    print(f"  check: {'OK' if rep['check']['passed'] else 'FAILED'} "
          f"{rep['check']['errors']}")
    return 0 if rep["check"]["passed"] or not args.check else 1


if __name__ == "__main__":
    import sys
    sys.exit(main(sys.argv[1:]))
