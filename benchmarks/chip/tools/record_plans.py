"""Record the plan histograms and payload table that the mixed-reuse
traffic files freeze.

Run once on the CPU from the root of the repository:

    PYTHONPATH=src:. JAX_PLATFORMS=cpu python benchmarks/chip/tools/record_plans.py OUT.json

It drives the repository's own ``ViTMAlisReuse`` policy (K = 4) through
``offload.simulator.Simulation`` at SIM size (the same 16-region
decision grid as ViTDet-L) on the ``parkS`` (static) and ``driveN``
(moving) scenarios over one 4G and one 5G trace each, and counts the
(n_low, n_reuse) of every offload after a session's full-res bootstrap.
The payload table is ``MixedResCodec.encode_size_only`` on 1024^2
frames of the same scenarios at quality 85, averaged over seeded region
choices.  The benchmark itself never imports this file.
"""
from __future__ import annotations

import json
import sys
from collections import Counter

import numpy as np

from benchmarks import bench_reuse as br
from benchmarks import common as C
from repro.configs.vitdet_l import CONFIG, SIM
from repro.core import vit_backbone as vb
from repro.data import synthetic_video as sv
from repro.offload.codec import MixedResCodec
from repro.serve.edge import BatchedServerModel

N_FRAMES = 120
QUALITY = 85
PAYLOAD_SAMPLES = 6


def record_plans() -> dict:
    params = C.get_server().params
    server = BatchedServerModel(SIM, params, top_k=32, score_thresh=0.4)
    part = vb.vit_partition(SIM)
    size_e, acc_e = br.build_estimators(server, 6, mlp_steps=1200)
    make_pol = br.policy_factories(size_e, acc_e)["ViTMAlis+Reuse"]
    out = {}
    for video in ("parkS", "driveN"):
        frames, _ = sv.make_clip(video, N_FRAMES, size=br.SIZE, seed=23)
        gt = [server.infer(f) for f in frames]
        counts: Counter = Counter()
        betas: Counter = Counter()
        for seed in (0, 1):
            pol = make_pol()
            decide = pol.decide
            seen = []

            def spy(sim, fi, decide=decide, seen=seen):
                d = decide(sim, fi)
                seen.append(d)
                return d
            pol.decide = spy
            sim = br._sim(server, part, frames, gt, pol, seed=seed)
            sim.trace = br.make_trace("4g" if seed == 0 else "5g", seed,
                                      duration_s=240)
            sim.run(video)
            for d in seen[1:]:                 # after the bootstrap
                plan = d.get("plan")
                if plan is None:
                    continue
                counts[(int(plan.n_low), int(plan.n_reuse))] += 1
                betas[int(d["beta"])] += 1
        out[video] = {"counts": [[nl, nr, c] for (nl, nr), c
                                 in sorted(counts.items())],
                      "betas": dict(sorted(betas.items()))}
    return out


def payload_table() -> dict:
    part = vb.vit_partition(CONFIG)
    codec = MixedResCodec(part, CONFIG.vit.patch_size, part.downsample)
    size = CONFIG.vit.img_size[0]
    frames = []
    for video in ("parkS", "driveN"):
        fs, _ = sv.make_clip(video, 3, size=size, seed=5)
        frames += list(fs[1:])
    rng = np.random.default_rng(0)
    nR = part.n_regions
    table = []
    for n_low in range(nR + 1):
        for n_reuse in range(nR + 1 - n_low):
            if n_low + n_reuse == nR and n_low == 0:
                continue                        # nothing transmitted
            sizes = []
            for k in range(PAYLOAD_SAMPLES):
                order = rng.permutation(nR)
                mask = np.zeros(nR, np.int32)
                mask[order[:n_low]] = 1
                reuse = np.zeros(nR, bool)
                reuse[order[n_low:n_low + n_reuse]] = True
                f = frames[k % len(frames)]
                sizes.append(codec.encode_size_only(f, mask, QUALITY,
                                                    reuse))
            table.append([n_low, n_reuse, int(round(np.mean(sizes)))])
    return {"quality": QUALITY, "bytes": table}


if __name__ == "__main__":
    result = {"plans": record_plans(), "payload": payload_table()}
    with open(sys.argv[1], "w") as f:
        json.dump(result, f)
    print(json.dumps(result))
