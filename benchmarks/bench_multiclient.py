"""Multi-client edge-serving benchmark — batched waves vs. sequential,
barrier vs. continuous scheduling.

Emits ``BENCH_multiclient.json`` with one row per (n_clients, mode):

  * ``throughput_fps``   — offloaded frames served per second of
                           SIMULATED time (the edge-capacity metric);
  * ``p50_e2e_s`` / ``p95_e2e_s`` — Eq. (2) end-to-end latency incl.
                           queueing delay at the shared replica;
  * ``p50_queue_s`` / ``p95_queue_s`` — queueing delay percentiles,
                           with the per-job BREAKDOWN surfaced as
                           ``p50_queue_admit_s`` (arrival -> bound to a
                           wave) + ``p50_queue_slot_s`` (bound ->
                           compute start);
  * ``device_idle_frac`` / ``decode_hidden_s`` / ``mean_wave`` —
                           scheduler telemetry (the continuous policy's
                           overlap win);
  * ``wall_s``           — real wall-clock of the run (the batched
                           forward also wins real compute time).

Modes: ``batched`` (barrier waves of same-(n_low bucket, beta) frames
through one batched ``forward_det``), ``continuous`` (same waves, but
decode/h2d staging overlapped under compute and late admission into
padded B-bucket slots — serve/scheduler.py), and ``sequential`` (one
frame per wave) on the SAME workload.  The harness also cross-checks
that all three modes' detections match box-for-box.

A final slow-uplink section re-runs a reuse-heavy 4-client workload
(3x parkS + driveN, rotating REUSE plans) over a compounded
bufferbloat overlay (offload/faults.py) in three modes — barrier,
continuous, and continuous+speculative — surfacing the speculative
REUSE lane's telemetry (``spec_launched``/``spec_patched``/
``spec_discarded`` and p50/p95 ``spec_hidden_s``, the transmission
time hidden behind the spliced forward) in every row.

Standalone:  python benchmarks/bench_multiclient.py [--smoke] [--out P]
Harness:     picked up by benchmarks/run.py as the ``bench_multiclient``
suite (smoke settings).
"""
from __future__ import annotations

import argparse
import json
import time
from pathlib import Path
from typing import Dict, List, Optional, Sequence

import jax
import numpy as np

from repro import compile_cache
from repro.configs.vitdet_l import SIM
from repro.core import vit_backbone as vb
from repro.data import synthetic_video as sv
from repro.data.network_traces import make_trace
from repro.models import registry
from repro.offload.faults import FaultInjector, FaultSpec, FaultyTrace
from repro.offload.optimizer import build_reuse_plan
from repro.offload.simulator import Policy, Simulation
from repro.serve.edge import (BatchedServerModel, EdgeConfig,
                              MultiClientSimulation)

DEFAULT_OUT = Path(__file__).resolve().parent.parent / \
    "BENCH_multiclient.json"
CLIENT_COUNTS = (1, 2, 4)
VIDEOS = ("walkS", "cycleS", "driveN", "walkB")
PATCH = SIM.vit.patch_size
SIZE = SIM.vit.img_size[0]
FPS = 10
# shared-edge service time: the paper's measured full-res ViTDet-L
# delay, scaled by the mixed-res FLOP ratio (offload/estimator.py does
# the same for the single-client grid) — makes the replica a genuine
# bottleneck so queueing/batching behaviour is visible
FULL_RES_DELAY_S = 0.281


class RotatingMaskPolicy(Policy):
    """Deterministic per-client region layout (distinct across clients,
    same n_low bucket) — the co-batching worst case for the old
    shared-layout packing."""
    name = "rotating"
    use_tracker = True

    def __init__(self, offset: int, n_low: int, n_regions: int,
                 beta: int = 2):
        self.offset = offset
        self.n_low = n_low
        self.n_regions = n_regions
        self.beta = beta

    def decide(self, sim: Simulation, frame_idx: int) -> Dict:
        mask = np.zeros(self.n_regions, np.int32)
        for k in range(self.n_low):
            mask[(self.offset + k) % self.n_regions] = 1
        return {"mask": mask, "quality": 85, "beta": self.beta}


class ReuseRotatingPolicy(Policy):
    """Rotating low mask + motion-gated REUSE lift (offload/optimizer.py)
    — the reuse-heavy workload the speculative lane targets."""
    name = "reuse-rotating"
    use_tracker = True
    reuse_k = 4

    def __init__(self, offset: int, n_low: int, n_regions: int,
                 beta: int = 2):
        self.offset = offset
        self.n_low = n_low
        self.n_regions = n_regions
        self.beta = beta

    def decide(self, sim: Simulation, frame_idx: int) -> Dict:
        mask = np.zeros(self.n_regions, np.int32)
        for k in range(self.n_low):
            mask[(self.offset + k) % self.n_regions] = 1
        cache = sim.feature_cache
        elig = (cache.eligible(self.beta) if cache is not None
                else np.zeros(self.n_regions, bool))
        plan = build_reuse_plan(sim.part, mask, sim.m, elig)
        return {"mask": mask, "quality": 85, "beta": self.beta,
                "plan": plan, "capture_beta": self.beta}


def _inf_delay_model():
    from repro.core import partition as pt
    from repro.offload.estimator import InferenceDelayModel
    part = vb.vit_partition(SIM)
    # cost the padded length bucket the collapsed grid actually serves
    edges = pt.length_bucket_set(part)
    return InferenceDelayModel.fit_from_flops(
        lambda n, b: vb.backbone_flops(SIM, n, b, length_edges=edges),
        part.n_regions,
        betas=tuple(range(SIM.vit.n_subsets + 1)),
        full_res_delay_s=FULL_RES_DELAY_S)


def make_clients(server: BatchedServerModel, n_clients: int,
                 n_frames: int, gt_cache: Dict) -> List[Simulation]:
    part = vb.vit_partition(SIM)
    inf_delay = _inf_delay_model()
    n_low = part.n_regions // 4
    clients = []
    for i in range(n_clients):
        vname = VIDEOS[i % len(VIDEOS)]
        key = (vname, n_frames)
        if key not in gt_cache:
            frames, _ = sv.make_clip(vname, n_frames, size=SIZE, seed=17)
            gt_cache[key] = (frames, [server.infer(f) for f in frames])
        frames, gt = gt_cache[key]
        pol = RotatingMaskPolicy(offset=i * n_low, n_low=n_low,
                                 n_regions=part.n_regions)
        clients.append(Simulation(frames, gt, make_trace("4g", i,
                                                         duration_s=120),
                                  pol, server, part, PATCH, fps=FPS,
                                  inf_delay=inf_delay))
    return clients


# congested-cell uplink for the speculative lane: stacked bufferbloat
# windows COMPOUND (offload/faults.py dents throughput to 70 % per
# window), leaving ~3 % of the 4g uplink at ~4x RTT for the whole run —
# the regime where transmission dominates Eq. (2)
SLOW_UPLINK = FaultSpec(
    bufferbloat=tuple((0.0, 3600.0, 1.15) for _ in range(10)))
SLOW_VIDEOS = ("parkS", "parkS", "parkS", "driveN")


def make_slow_clients(server: BatchedServerModel, n_clients: int,
                      n_frames: int, gt_cache: Dict) -> List[Simulation]:
    part = vb.vit_partition(SIM)
    inf_delay = _inf_delay_model()
    n_low = part.n_regions // 4
    clients = []
    for i in range(n_clients):
        vname = SLOW_VIDEOS[i % len(SLOW_VIDEOS)]
        key = (vname, n_frames)
        if key not in gt_cache:
            frames, _ = sv.make_clip(vname, n_frames, size=SIZE, seed=17)
            gt_cache[key] = (frames, [server.infer(f) for f in frames])
        frames, gt = gt_cache[key]
        pol = ReuseRotatingPolicy(offset=i * n_low, n_low=n_low,
                                  n_regions=part.n_regions)
        trace = FaultyTrace(make_trace("4g", i, duration_s=240),
                            FaultInjector(SLOW_UPLINK))
        clients.append(Simulation(frames, gt, trace, pol, server, part,
                                  PATCH, fps=FPS, inf_delay=inf_delay))
    return clients


def run_mode(server: BatchedServerModel, n_clients: int, n_frames: int,
             batched: bool, gt_cache: Dict,
             scheduler: str = "barrier", speculate: bool = False,
             clients_fn=None, videos: Sequence[str] = VIDEOS) -> Dict:
    clients = (clients_fn or make_clients)(server, n_clients, n_frames,
                                           gt_cache)
    mc = MultiClientSimulation(clients, server,
                               EdgeConfig(batched=batched,
                                          scheduler=scheduler,
                                          speculate=speculate,
                                          keep_dets=True))
    t0 = time.perf_counter()
    results = mc.run([videos[i % len(videos)] for i in range(n_clients)])
    wall = time.perf_counter() - t0

    e2e = np.array([x for r in results for x in r.e2e_latency], np.float64)
    queue = np.asarray(mc.stats.queue_delays, np.float64)
    admit = np.asarray(mc.stats.queue_admit, np.float64)
    slot = np.asarray(mc.stats.queue_slot, np.float64)
    sim_seconds = n_frames / FPS

    def p(x, q):
        return float(np.percentile(x, q)) if x.size else 0.0

    return {
        "n_clients": n_clients,
        "mode": ("continuous+speculative" if speculate
                 else "continuous" if scheduler == "continuous"
                 else "batched" if batched else "sequential"),
        "offloads": int(e2e.size),
        "throughput_fps": float(e2e.size / sim_seconds),
        "p50_e2e_s": float(np.percentile(e2e, 50)) if e2e.size else None,
        "p95_e2e_s": float(np.percentile(e2e, 95)) if e2e.size else None,
        "p50_queue_s": p(queue, 50),
        "p95_queue_s": p(queue, 95),
        "p50_queue_admit_s": p(admit, 50),
        "p50_queue_slot_s": p(slot, 50),
        "device_idle_frac": mc.stats.device_idle_frac,
        "decode_hidden_s": mc.stats.decode_hidden_s,
        "mean_wave": mc.stats.mean_wave_size,
        "spec_launched": mc.stats.spec_launched,
        "spec_patched": mc.stats.spec_patched,
        "spec_discarded": mc.stats.spec_discarded,
        "spec_hidden_s": mc.stats.spec_hidden_s,
        "p50_spec_hidden_s": mc.stats.spec_hidden_percentile(50),
        "p95_spec_hidden_s": mc.stats.spec_hidden_percentile(95),
        "wall_s": wall,
        "_jobs": {f"{j['client']}:{j['frame']}": j["dets"]
                  for j in mc.stats.jobs},
    }


def _dets_close(a: List[Dict], b: List[Dict], atol: float = 0.5) -> bool:
    if len(a) != len(b):
        return False
    for da, db in zip(a, b):
        if da["cls"] != db["cls"]:
            return False
        if not np.allclose(np.asarray(da["box"], np.float64),
                           np.asarray(db["box"], np.float64), atol=atol):
            return False
    return True


def run_slow_uplink(server: BatchedServerModel,
                    n_frames: int) -> List[Dict]:
    """Barrier vs continuous vs continuous+speculative on the same
    reuse-heavy 4-client workload over the SLOW_UPLINK overlay — the
    uplink-dominated regime where the speculative lane hides the
    payload transit behind the spliced forward."""
    gt_cache: Dict = {}
    rows = []
    for scheduler, speculate in (("barrier", False),
                                 ("continuous", False),
                                 ("continuous", True)):
        row = run_mode(server, len(SLOW_VIDEOS), n_frames, batched=True,
                       gt_cache=gt_cache, scheduler=scheduler,
                       speculate=speculate, clients_fn=make_slow_clients,
                       videos=SLOW_VIDEOS)
        row.pop("_jobs")
        row["uplink"] = "slow"
        rows.append(row)
    return rows


def run_bench(smoke: bool = False, out: Path = DEFAULT_OUT,
              client_counts: Sequence[int] = CLIENT_COUNTS) -> dict:
    n_frames = 16 if smoke else 48
    params = registry.init_params(SIM, jax.random.PRNGKey(0))
    server = BatchedServerModel(SIM, params, top_k=8, score_thresh=0.0)
    gt_cache: Dict = {}

    rows, match = [], {}
    for n in client_counts:
        row_b = run_mode(server, n, n_frames, batched=True,
                         gt_cache=gt_cache)
        row_c = run_mode(server, n, n_frames, batched=True,
                         gt_cache=gt_cache, scheduler="continuous")
        row_s = run_mode(server, n, n_frames, batched=False,
                         gt_cache=gt_cache)
        jobs_b = row_b.pop("_jobs")
        jobs_c = row_c.pop("_jobs")
        jobs_s = row_s.pop("_jobs")
        shared = set(jobs_b) & set(jobs_s)
        shared_c = set(jobs_b) & set(jobs_c)
        match[n] = {
            "compared": len(shared),
            "all_match": bool(shared) and all(
                _dets_close(jobs_b[k], jobs_s[k]) for k in shared),
            "compared_continuous": len(shared_c),
            "continuous_match": bool(shared_c) and all(
                _dets_close(jobs_b[k], jobs_c[k]) for k in shared_c),
        }
        rows.extend([row_b, row_c, row_s])

    slow_rows = run_slow_uplink(server, max(n_frames, 40))
    rows.extend(slow_rows)

    report = {
        "meta": {
            "config": "vitdet-l/SIM",
            "device": jax.default_backend(),
            "smoke": smoke,
            "n_frames": n_frames,
            "fps": FPS,
            "full_res_delay_s": FULL_RES_DELAY_S,
            "batch_alpha": EdgeConfig().batch_alpha,
        },
        "rows": rows,
        "detections_match": match,
    }
    out.write_text(json.dumps(report, indent=2) + "\n")
    print(f"[bench_multiclient] wrote {out}")
    return report


def run(ctx: dict) -> list:
    """benchmarks/run.py adapter: smoke settings, CSV rows."""
    out = Path(__file__).resolve().parent / "artifacts"
    out.mkdir(parents=True, exist_ok=True)
    rep = run_bench(smoke=True, out=out / "BENCH_multiclient.smoke.json",
                    client_counts=(1, 2))
    rows = []
    for r in rep["rows"]:
        tag = "/slow" if r.get("uplink") == "slow" else ""
        rows.append((f"bench_multiclient/{r['n_clients']}c"
                     f"/{r['mode']}{tag}",
                     r["throughput_fps"],
                     f"p95_e2e={r['p95_e2e_s']:.3f}s "
                     f"wave={r['mean_wave']:.2f}"))
    ctx["bench_multiclient"] = rows
    return rows


def main(argv=None) -> int:
    compile_cache.enable()
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true",
                    help="fewer frames/clients (CI sanity lane)")
    ap.add_argument("--clients", type=int, nargs="*", default=None,
                    help=f"client counts (default {CLIENT_COUNTS})")
    ap.add_argument("--out", type=Path, default=DEFAULT_OUT,
                    help=f"output JSON path (default {DEFAULT_OUT})")
    args = ap.parse_args(argv)
    counts = tuple(args.clients) if args.clients else CLIENT_COUNTS
    rep = run_bench(smoke=args.smoke, out=args.out, client_counts=counts)
    for r in rep["rows"]:
        extra = ""
        if r.get("uplink") == "slow":
            extra = (f"  [slow uplink] spec L/P/D "
                     f"{r['spec_launched']}/{r['spec_patched']}"
                     f"/{r['spec_discarded']} "
                     f"hidden p50 {r['p50_spec_hidden_s']:.3f}s")
        print(f"  {r['n_clients']}c {r['mode']:>22}: "
              f"{r['throughput_fps']:6.2f} offloads/s  "
              f"p50 {r['p50_e2e_s']:.3f}s  p95 {r['p95_e2e_s']:.3f}s  "
              f"queue p50 {r['p50_queue_s']:.3f}s  "
              f"wave {r['mean_wave']:.2f}{extra}")
    for n, m in rep["detections_match"].items():
        print(f"  {n}c detections batched==sequential: {m['all_match']} "
              f"({m['compared']} jobs)  "
              f"continuous==batched: {m['continuous_match']} "
              f"({m['compared_continuous']} jobs)")
    return 0


if __name__ == "__main__":
    import sys
    sys.exit(main())
