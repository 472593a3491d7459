"""The plain reference against the served Pallas lane (interpret mode)
at SIM size: a full-res bootstrap that captures at beta, then a wave
whose plans hold LOW and REUSE regions over the warmed cache."""
import json
import sys
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
from chipbench import check as C  # noqa: E402
from chipbench import reference as R  # noqa: E402
from chipbench import traffic as T  # noqa: E402
from chipbench import weights as Wt  # noqa: E402
from chipbench.harness import program_config  # noqa: E402

SIM = json.loads((BENCH / "tests" / "data" / "sim.json").read_text())


def test_weight_tree_is_the_programs():
    import jax
    from repro.models import registry
    model = SIM["model"]
    ours = Wt.make(model, 3)
    theirs = registry.init_params(program_config(model),
                                  jax.random.PRNGKey(0))
    a = jax.tree_util.tree_structure(ours)
    b = jax.tree_util.tree_structure(theirs)
    assert a == b
    for x, y in zip(jax.tree_util.tree_leaves(ours),
                    jax.tree_util.tree_leaves(theirs)):
        assert x.shape == y.shape and x.dtype == y.dtype
    again = Wt.make(model, 3)
    assert all(np.array_equal(x, y) for x, y in zip(
        jax.tree_util.tree_leaves(ours), jax.tree_util.tree_leaves(again)))


def _states(**kw):
    st = np.full((16,), T.FULL, np.int8)
    for state, regions in kw.items():
        st[regions] = {"low": T.LOW, "reuse": T.REUSE}[state]
    return st


class _Session:
    def __init__(self, sid, offloads):
        self.sid, self.offloads = sid, offloads


def test_reference_matches_served_pallas_lane():
    from repro.core.partition import RegionPlan
    from repro.offload.simulator import ServerModel
    from repro.serve.request import FeatureCache
    model, beta, top_k = SIM["model"], SIM["serving"]["beta"], 8
    params = Wt.make(model, 11)
    sm = ServerModel(program_config(model), params, top_k=top_k,
                     score_thresh=0.0, backend="pallas", b_buckets=(2,))
    rng = np.random.default_rng(0)
    frames = rng.random((4, 256, 256, 3), dtype=np.float32)
    plans = {0: [_states(), _states(low=[0, 1, 2], reuse=[5, 6, 9, 10])],
             1: [_states(), _states(low=[3, 7, 11, 12, 13, 14, 15])]}
    offs = {s: [T.Offload(s, k, 2 * k + s, st, 0)
                for k, st in enumerate(seq)] for s, seq in plans.items()}
    caches = [FeatureCache(16), FeatureCache(16)]
    served = {}
    for k in range(2):
        ps = [RegionPlan(offs[s][k].states) for s in (0, 1)]
        pending = sm.infer_wave(
            sm.stage_frames(frames[[offs[s][k].frame for s in (0, 1)]]),
            ps, beta, caches=caches, frame_ids=[k, k], capture_beta=beta,
            defer=True)
        pending.wait()
        for s in (0, 1):
            served[(s, k)] = (np.asarray(pending.scores[s]),
                              np.asarray(pending.boxes[s]))
    assert sm.stats.reuse_splices == 1
    g = R.Geometry.from_config(model)
    tiles = {s: (1, R.tiles_to_grid(np.asarray(caches[s].tiles), g))
             for s in (0, 1)}
    ref = C.Reference(params, g, beta, top_k, frames,
                      [_Session(s, offs[s]) for s in (0, 1)], True)
    numbers = C.compare(ref, served, tiles)
    assert numbers["tiles_rel_l2"] < 1e-5, numbers
    assert numbers["score_rel_rms"] < 1e-5, numbers
    assert numbers["box_rel_rms"] < 1e-5, numbers
    # the reused regions' tiles are the bootstrap's, spliced unchanged
    boot = np.asarray(ref.restored(0, 0))
    rm = R.region_map(g)
    reused = np.isin(rm, [5, 6, 9, 10])
    np.testing.assert_allclose(tiles[0][1][reused], boot[reused],
                               rtol=1e-5, atol=1e-5)
    # and a frame served without its REUSE splice is caught
    bad = dict(tiles)
    bad[0] = (1, np.where(reused[..., None], 0.0, tiles[0][1]))
    assert C.compare(ref, served, bad)["tiles_rel_l2"] > 0.1
