"""The traffic generator offers the same work for one seed."""
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
from chipbench import traffic as T  # noqa: E402


def _deal(name, seed, n=40):
    tr = T.load(BENCH / name)
    out = []
    for s in T.sessions(tr, 16, seed):
        offs = [s.next_offload() for _ in range(n)]
        out.append((s.kind, s.tput_bps.tolist(),
                    [(o.frame, o.states.tolist(), o.payload_bytes)
                     for o in offs],
                    [s.think_s(o, 3.7 * i) for i, o in enumerate(offs)]))
    return out, tr


@pytest.mark.parametrize("name", ["traffic/mixed-reuse-s8.json",
                                  "tests/data/sim-mixed.json"])
def test_same_seed_same_offers(name):
    a, tr = _deal(name, 2 ** 33 + 17)
    b, _ = _deal(name, 2 ** 33 + 17)
    assert a == b
    c, _ = _deal(name, 2 ** 33 + 18)
    assert a != c
    assert len(a) == tr["sessions"]


def test_every_seed_deals_the_same_mix():
    """Each block of PLAN_BLOCK plans is a stratified draw: per block, an
    entry of the histogram comes up within two of its share."""
    tr = T.load(BENCH / "traffic" / "mixed-reuse-s8.json")
    a, b = (T.sessions(tr, 16, seed) for seed in (3, 2 ** 33 + 5))
    # the same traces and capture slots, whatever the seed
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x.tput_bps, y.tput_bps)
        assert (x.kind, x.phase_s) == (y.kind, y.phase_s)
    for seed in (3, 2 ** 33 + 5):
        for s in T.sessions(tr, 16, seed):
            hist = np.asarray(tr["plans"][s.kind])
            share = hist[:, 2] / hist[:, 2].sum() * T.PLAN_BLOCK
            for _ in range(5):
                block = [tuple(int(x) for x in s._draw())
                         for _ in range(T.PLAN_BLOCK)]
                got = np.array([block.count(tuple(h[:2])) for h in hist])
                assert (np.abs(got - share) < 2).all(), (got, share)


def test_camera_clock_fixes_the_offered_rate():
    tr = dict(T.load(BENCH / "traffic" / "mixed-reuse-s8.json"),
              frame_interval_s=0.8)
    ss = T.sessions(tr, 16, 2 ** 33 + 1)
    phases = sorted(s.phase_s for s in ss)
    np.testing.assert_allclose(phases, np.arange(8) * 0.1)
    s = ss[0]
    p = s.phase_s
    assert s.capture_after(0.0) == p
    assert s.capture_after(p + 0.8) == p + 0.8          # on a capture
    assert abs(s.capture_after(p + 0.81) - (p + 1.6)) < 1e-9
    off = s.next_offload()
    assert s.due_after(off, 0.81) == s.capture_after(0.81) + s.think_s(
        off, s.capture_after(0.81))
    # without a camera clock a session captures at once
    free = T.sessions(dict(tr, frame_interval_s=None), 16, 2 ** 33 + 1)[0]
    assert free.capture_after(3.3) == 3.3


def test_reuse_respects_staleness_and_transmits():
    tr = T.load(BENCH / "traffic" / "mixed-reuse-s8.json")
    for s in T.sessions(tr, 16, 7):
        age = np.zeros(16, int)
        for k in range(200):
            o = s.next_offload()
            if k == 0:
                assert (o.states == T.FULL).all()     # bootstrap
            reuse = o.states == T.REUSE
            assert (age[reuse] < tr["reuse_k"]).all()
            assert (~reuse).any()
            age = np.where(reuse, age + 1, 0)


def test_frames_and_traces_repeat():
    tr = T.load(BENCH / "traffic" / "mixed-reuse-s8.json")
    tr = dict(tr, frame_pool=2)
    np.testing.assert_array_equal(T.frame_pool(tr, 32, 5),
                                  T.frame_pool(tr, 32, 5))
    t1, r1 = T.make_trace("4g", 3, 60)
    t2, r2 = T.make_trace("4g", 3, 60)
    np.testing.assert_array_equal(t1, t2)
    assert (t1 > 0).all() and (r1 >= 0.015).all()


def test_trace_copy_matches_program():
    from repro.data.network_traces import make_trace
    for kind, idx in (("4g", 0), ("5g", 7)):
        ours = T.make_trace(kind, idx, 300)
        theirs = make_trace(kind, idx, 300)
        np.testing.assert_array_equal(ours[0], theirs.tput_bps)
        np.testing.assert_array_equal(ours[1], theirs.rtt_s)
