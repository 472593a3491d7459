"""The in-program span recorder (repro.spans) and the serving path's
spans, counts and device scopes, at SIM size on the CPU."""
import contextlib
import glob

import jax
import numpy as np
import pytest

from repro import spans
from repro.configs.vitdet_l import SIM
from repro.core import partition as pt
from repro.core import vit_backbone as vb
from repro.core.partition import LOW, REUSE, RegionPlan
from repro.models import registry
from repro.offload.simulator import ServerModel
from repro.serve.request import FeatureCache

SIZE = SIM.vit.img_size[0]


@pytest.fixture(autouse=True)
def fresh_recorder():
    spans.disable()
    spans.clear()
    yield
    spans.disable()
    spans.clear()


@pytest.fixture(scope="module")
def setup():
    params = registry.init_params(SIM, jax.random.PRNGKey(0))
    return params, vb.vit_partition(SIM)


def _frames(n, seed=0):
    rng = np.random.default_rng(seed)
    return rng.uniform(0, 1, (n, SIZE, SIZE, 3)).astype(np.float32)


def _plan(part, low=(), reuse=()):
    st = np.zeros((part.n_regions,), np.int8)
    st[list(low)] = LOW
    st[list(reuse)] = REUSE
    return RegionPlan(st)


# ---------------------------------------------------------------------------
# the recorder


def test_off_records_nothing():
    assert not spans.recording()
    with spans.span("serve.stage", spans.NEW) as sp:
        sp.add("h2d")
    assert sp is spans.OFF and sp.wave is None
    assert spans.recorded() == []


def test_enable_records_nested_spans_of_one_wave():
    spans.enable()
    with spans.span("serve.infer_wave", spans.NEW) as root:
        with spans.span("serve.args") as child:
            child.add("h2d", 8)
            with spans.span("serve.leaf") as leaf:
                pass
    spans.disable()
    with spans.span("serve.after"):
        pass
    got = {s.name: s for s in spans.recorded()}
    assert set(got) == {"serve.infer_wave", "serve.args", "serve.leaf"}
    assert root.parent == 0
    assert child.parent == root.sid and leaf.parent == child.sid
    assert root.wave is not None
    assert child.wave is root.wave and leaf.wave is root.wave
    assert child.counts == {"h2d": 8} and child.calls == 8
    assert root.t0 <= child.t0 <= leaf.t0 <= leaf.t1 <= child.t1 <= root.t1
    # a span names its wave's offloads through the wave it shares
    root.wave.offloads = ((3, 7),)
    assert leaf.wave.offloads == ((3, 7),)


def test_ring_is_bounded():
    spans.enable()
    first = None
    for i in range(spans.RING + 5):
        with spans.span("serve.stage", spans.NEW) as sp:
            pass
        first = first or sp
    rec = spans.recorded()
    assert len(rec) == spans.RING
    assert first not in rec and rec[-1] is sp


def test_profiler_session_turns_recording_on(tmp_path):
    """Pins jax's ``_profile_state.profile_session``: None without a
    trace, set by start_trace, reset by stop_trace."""
    from jax._src import profiler as jp
    assert jp._profile_state.profile_session is None
    assert not spans.recording()
    jax.profiler.start_trace(str(tmp_path))
    try:
        assert jp._profile_state.profile_session is not None
        assert spans.recording()
        with spans.span("serve.stage", spans.NEW):
            pass
    finally:
        jax.profiler.stop_trace()
    assert jp._profile_state.profile_session is None
    assert not spans.recording()
    with spans.span("serve.after"):
        pass
    assert [s.name for s in spans.recorded()] == ["serve.stage"]


# ---------------------------------------------------------------------------
# the serving path


def _trace_executions(trace_dir):
    """Device computations run in a CPU trace (distinct run ids)."""
    from jax.profiler import ProfileData
    path = sorted(glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb"))
    runs = set()
    for plane in ProfileData.from_file(path[-1]).planes:
        for ln in plane.lines:
            for ev in ln.events:
                st = {str(k): v for k, v in ev.stats}
                if "hlo_module" in st and "run_id" in st:
                    runs.add(st["run_id"])
    return len(runs)


def test_deferred_reuse_wave_span_tree_and_device_calls(setup, tmp_path):
    """One deferred B=2 wave (a REUSE row and a LOW row) and its wait
    form one tree per root, all of one wave, and the calls counted on
    its spans are the hand count; the launches among them are the
    computations a trace of the same wave records."""
    params, part = setup
    server = ServerModel(SIM, params, top_k=8, score_thresh=0.0,
                         b_buckets=(2,))
    frames = _frames(2)
    low = _plan(part, low=range(4))
    caches = [FeatureCache(part.n_regions, max_age=4) for _ in range(2)]
    server.infer_wave(frames, [low, low], beta=2, caches=caches,
                      frame_ids=[0, 0], capture_beta=2)
    plans = [_plan(part, low=range(4), reuse=range(8, 12)), low]
    # every computation of the wave compiled before the trace
    server.infer_wave(frames, plans, beta=2, caches=caches,
                      frame_ids=[1, 1], capture_beta=2)
    spans.clear()
    jax.profiler.start_trace(str(tmp_path))
    try:
        staged = server.stage_frames(frames)
        pending = server.infer_wave(staged, plans, beta=2, caches=caches,
                                    frame_ids=[2, 2], capture_beta=2,
                                    defer=True, sessions=[5, 6])
        dets = pending.wait()
    finally:
        jax.profiler.stop_trace()
    assert len(dets) == 2
    rec = spans.recorded()
    by_sid = {s.sid: s for s in rec}
    tree = {}
    for s in rec:
        parent = by_sid[s.parent].name if s.parent else None
        tree.setdefault(parent, []).append(s.name)
    assert tree[None] == ["serve.stage", "serve.infer_wave", "serve.wait"]
    assert tree["serve.infer_wave"] == [
        "serve.layout", "serve.tiles", "serve.args", "serve.launch",
        "serve.cache_refresh"]
    assert tree["serve.wait"] == ["serve.ready", "serve.decode"]
    assert len({id(s.wave) for s in rec}) == 1
    assert rec[0].wave.offloads == ((5, 2), (6, 2))

    counts = {}
    for s in rec:
        for k, n in s.counts.items():
            counts[(s.name, k)] = n
    # by hand: the frames' h2d; the REUSE row's ids h2d and gather, two
    # computations for the LOW row's zero tiles, and a stack of two rows
    # (two expands, a concatenate); eight layout arrays; the executable;
    # per row a take and an in-place refresh; per row and array of the
    # three outputs a slice (two computations, the first row's before
    # the block) and a read
    assert counts == {
        ("serve.stage", "h2d"): 1,
        # 36 and 52 windows of 4 tokens at length bucket 64
        ("serve.layout", "keys"): 2 * 64 * 4,
        ("serve.layout", "pad_keys"): (28 + 12) * 4,
        ("serve.tiles", "h2d"): 1, ("serve.tiles", "jit_launches"): 6,
        ("serve.args", "h2d"): 8,
        ("serve.launch", "launches"): 1,
        ("serve.cache_refresh", "jit_launches"): 4,
        ("serve.ready", "jit_launches"): 6,
        ("serve.decode", "jit_launches"): 6, ("serve.decode", "d2h"): 6}
    assert sum(s.calls for s in rec) == 39
    launched = sum(n for (_, k), n in counts.items()
                   if k in ("launches", "jit_launches"))
    assert _trace_executions(tmp_path) == launched == 23


def test_layout_counts_keys_and_pad_keys_of_real_rows(setup):
    """``serve.layout`` counts the key slots of the wave's real rows at
    its length bucket and the pad keys among them, from the plans'
    window counts; a row that only pads the B bucket counts nothing."""
    params, part = setup
    server = ServerModel(SIM, params, top_k=8, score_thresh=0.0,
                         b_buckets=(2,))
    w2 = part.window ** 2
    waves = [([_plan(part, low=range(12)), _plan(part, low=range(16))],
              48, [28, 16]),
             ([_plan(part, low=range(16))], 24, [16])]
    for plans, lb, nws in waves:
        assert [pt.plan_n_windows(p, part) for p in plans] == nws
        spans.enable()
        server.infer_wave(_frames(len(plans)), plans, beta=2)
        spans.disable()
        (lay,) = [s for s in spans.recorded() if s.name == "serve.layout"]
        spans.clear()
        assert lay.counts == {"keys": len(plans) * lb * w2,
                              "pad_keys": sum(lb - n for n in nws) * w2}
        assert lay.calls == 0


def test_named_scopes_leave_outputs_bit_identical(setup, monkeypatch):
    """The same wave through an executable compiled with the device
    scopes and one compiled without them: identical bits, and only the
    first carries the scopes in its HLO."""
    params, part = setup
    frames = _frames(2, seed=1)
    low = _plan(part, low=range(4))
    plans = [_plan(part, low=range(4), reuse=range(8, 12)), low]

    def serve(server):
        caches = [FeatureCache(part.n_regions, max_age=4) for _ in range(2)]
        server.infer_wave(frames, [low, low], beta=2, caches=caches,
                          frame_ids=[0, 0], capture_beta=2)
        p = server.infer_wave(server.stage_frames(frames), plans, beta=2,
                              caches=caches, frame_ids=[1, 1],
                              capture_beta=2, defer=True)
        fn = server._fns[(64, 2, 2, 2)]
        return ([np.asarray(a) for a in (p.boxes, p.scores, p.classes)]
                + [np.asarray(c.tiles) for c in caches]), fn.as_text()

    scoped, text = serve(ServerModel(SIM, params, top_k=8,
                                     score_thresh=0.0, b_buckets=(2,)))
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    plain, plain_text = serve(ServerModel(SIM, params, top_k=8,
                                          score_thresh=0.0,
                                          b_buckets=(2,)))
    for a, b in zip(scoped, plain):
        np.testing.assert_array_equal(a, b)
    for scope in spans.SCOPES:
        assert scope in text and scope not in plain_text


def test_scope_map_names_every_scope_of_the_grid(setup):
    """Each compiled executable has its own HLO module, whose
    instructions map to the three scopes, a restore and the blocks."""
    params, part = setup
    server = ServerModel(SIM, params, top_k=8, score_thresh=0.0,
                         b_buckets=(1,))
    server.warmup([(4, 4, 2, 2), (0, 0, 0, 2)])
    maps = spans.scope_map()
    for key in server._fns:
        lb, beta, cap, b = key
        name = f"jit_serve_lb{lb}_beta{beta}_cap{cap}_b{b}"
        scopes = set(maps[name].values())
        assert {"vit.pre_beta", "det.head"} <= {s.split("/")[0]
                                                 for s in scopes}
        assert any(s.startswith("vit.post_beta/block") for s in scopes)
        # SIM: 8 blocks in 4 subsets; beta 2 / capture 2 split at block 3
        assert not any(s in scopes for s in
                       ("vit.pre_beta/block03", "vit.post_beta/block02"))
        assert "vit.pre_beta/block02" in scopes
        assert "vit.post_beta/block03" in scopes
        if lb:
            assert "vit.post_beta/restore" in scopes
