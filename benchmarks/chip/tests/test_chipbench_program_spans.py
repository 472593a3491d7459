"""The readers of the program's own spans and device scopes: on
hand-made spans and device events, the trace's zero derived on a trace
recorded on the CPU, and the scope reduction on a CPU trace of a served
wave at SIM size."""
import sys
import time
from pathlib import Path
from types import SimpleNamespace as NS

import jax
import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
from chipbench import harness as H  # noqa: E402
from chipbench import program_spans as PS  # noqa: E402
from chipbench import tracing as TR  # noqa: E402
from repro import spans  # noqa: E402

NEW_READERS = ("dispatch_host_ms_per_wave", "decode_host_ms_per_wave",
               "device_calls_per_wave", "host_bound_idle_frac",
               "pre_beta_ms_per_frame", "post_beta_ms_per_frame",
               "head_ms_per_frame")


@pytest.fixture(autouse=True)
def fresh_recorder():
    spans.disable()
    spans.clear()
    yield
    spans.disable()
    spans.clear()


def _ev(name, s, e, **st):
    return (name, float(s), float(e), st)


class _Compiled:
    """An executable's HLO text, as ``spans.note_executable`` keeps it."""

    def __init__(self, text):
        self.text = text

    def as_text(self):
        return self.text


HLO = """HloModule jit_serve_hand, entry_computation_layout={()->f32[4]}

ENTRY %main.1 () -> f32[4] {
  %fusion.1 = f32[4]{0} fusion(), kind=kLoop, metadata={op_name="jit(serve_hand)/vit.pre_beta/block00/dot_general" stack_frame_id=1}
  %_flash_attention.2 = f32[4]{0} custom-call(), metadata={op_name="jit(serve_hand)/vit.post_beta/block03/jit(_flash_attention)/pallas_call"}
  %fusion.3 = f32[4]{0} fusion(), metadata={op_name="jit(serve_hand)/vit.post_beta/restore/gather"}
  ROOT %fusion.4 = f32[4]{0} fusion(), metadata={op_name="jit(serve_hand)/det.head/top_k"}
  %copy.5 = f32[4]{0} copy()
}
"""

MS = 1_000_000                 # ns


def _span(name, t0, t1, wave=None, **counts):
    """A recorded span with hand-made times (host clock, ns)."""
    with spans.span(name, wave) as sp:
        for k, n in counts.items():
            sp.add(k, n)
    sp.t0, sp.t1 = t0, t1
    return sp


def _hand_made():
    """Two waves 500 ms apart, the trace's zero at 100 s of the host
    clock, the traced span from 100.01 s to 101 s."""
    zero = 100.0
    z = int(zero * 1e9)
    spans.enable()
    host, waves, jobs, dev = [], [], [], []
    for w in range(2):
        t = z + 100 * MS + w * 500 * MS
        # the loop's stage span opens at its dispatch time (a clock read
        # later on the chip), the program's inside it
        host.append(("chipbench.stage", t - z, t + 3 * MS - z,
                     {"wave": w}))
        stage = _span("serve.stage", t, t + 2 * MS, spans.NEW, h2d=1)
        wave = stage.wave
        _span("serve.infer_wave", t + 2 * MS, t + 6 * MS, wave)
        _span("serve.args", t + 3 * MS, t + 4 * MS, wave, h2d=8)
        _span("serve.launch", t + 4 * MS, t + 5 * MS, wave, launches=1)
        _span("serve.wait", t + 50 * MS, t + 60 * MS, wave)
        _span("serve.decode", t + 58 * MS, t + 60 * MS, wave,
              jit_launches=6, d2h=3)
        waves.append((w, 1, 24, 0.004, (t - z) * 1e-9 + zero,
                      (t + 60 * MS - z) * 1e-9 + zero))
        jobs.append(NS(off=NS(n_low=4, n_reuse=2), due=0.0,
                       dispatch=waves[-1][4], done=waves[-1][5]))
        # device: busy from 5 ms to 55 ms after the dispatch
        d = t - z
        for k, name in enumerate(("%fusion.1 = f32[4]{0} fusion()",
                                  "%_flash_attention.2 = f32[4]{0} "
                                  "custom-call()",
                                  "%fusion.3 = f32[4]{0} fusion()",
                                  "%fusion.4 = f32[4]{0} fusion()",
                                  "%copy.5 = f32[4]{0} copy()")):
            dev.append(_ev(name, d + (5 + 10 * k) * MS,
                           d + (15 + 10 * k) * MS,
                           hlo_module="jit_serve_hand"))
    spans.disable()
    spans.note_executable("serve_hand", _Compiled(HLO))
    ctx = {"jobs": jobs, "waves": waves, "lo_s": 100.01, "hi_s": 101.0,
           "device_events": dev, "host_spans": host, "trace_lo_ns": 0.0,
           "trace_hi_ns": 1e9, "model": {}, "beta": 2,
           "device_kind": "TPU v5 lite", "traffic": {}}
    return ctx


def test_readers_on_hand_made_spans_and_device_events():
    ctx = _hand_made()
    got = {n: H.load_reader(n)(ctx) for n in NEW_READERS}
    assert PS.zero_s(ctx) == pytest.approx(100.0, abs=1e-12)
    # stage 2 ms, infer_wave 4 ms
    assert got["dispatch_host_ms_per_wave"] == pytest.approx(6.0)
    assert got["decode_host_ms_per_wave"] == pytest.approx(2.0)
    assert got["device_calls_per_wave"] == 1 + 8 + 1 + 9
    # per wave the program's spans cover [0, 6] and [50, 60] ms after
    # the dispatch; the device is idle in [0, 5] and [55, 500]: 5 + 5 ms
    # of each wave in the 1 s traced span
    assert got["host_bound_idle_frac"] == pytest.approx(2.0)
    # per frame (one a wave): 10 ms of fusion.1; the flash call and the
    # restore gather; fusion.4
    assert got["pre_beta_ms_per_frame"] == pytest.approx(10.0)
    assert got["post_beta_ms_per_frame"] == pytest.approx(20.0)
    assert got["head_ms_per_frame"] == pytest.approx(10.0)
    waves = PS.waves(PS.in_span(ctx))
    assert [len(w) for w in waves.values()] == [6, 6]


def test_readers_find_nothing_without_program_spans():
    ctx = _hand_made()
    spans.clear()
    assert all(H.load_reader(n)(ctx) is None for n in NEW_READERS)


def test_scopes_on_module_events_without_module_stats():
    """A TPU trace names no module on its ops: each op takes the module
    of the module event that encloses it."""
    spans.note_executable("serve_hand", _Compiled(HLO))
    dev = [_ev("%fusion.1 = f32[4]{0} fusion()", 10, 20),
           _ev("%fusion.4 = f32[4]{0} fusion()", 30, 40),
           _ev("%fusion.1 = f32[4]{0} fusion()", 60, 70)]
    mods = [(5.0, 45.0, "jit_serve_hand"), (55.0, 80.0, "jit_other")]
    assert PS.op_scopes(dev, mods) == [("vit.pre_beta/block00", 10, 20),
                                       ("det.head", 30, 40)]


def test_zero_derivation_on_a_cpu_trace(tmp_path):
    """The program's spans, put on the trace's clock through the zero
    derived from the loop's waves and stage spans, enclose the ops they
    ran (the tolerance of test_logged_spans_on_the_trace_clock)."""
    import jax.numpy as jnp
    x = jnp.ones((512, 512))
    f = jax.jit(lambda a: (a @ a @ a).sum())
    f(x).block_until_ready()
    log, waves = [], []
    zero = time.perf_counter()
    jax.profiler.start_trace(str(tmp_path))
    tol = (time.perf_counter() - zero) * 1e9 + 1e6
    lo = time.perf_counter()
    for i in range(3):
        t0 = time.perf_counter()
        with H.span(log, "stage", wave=i):
            with spans.span("serve.stage", spans.NEW) as sp:
                pass
        with spans.span("serve.infer_wave", sp.wave):
            f(x).block_until_ready()
        waves.append((i, 1, 24, 0.0, t0, time.perf_counter()))
        time.sleep(0.02)
    hi_s = time.perf_counter()
    jax.profiler.stop_trace()
    hi = (hi_s - zero) * 1e9
    dev, _, layout = TR.read(TR.latest_xplane(str(tmp_path)),
                             device_line="tf_XLA", device_plane="/host:CPU")
    ctx = {"host_spans": TR.rebase(log, zero, 0.0, hi), "waves": waves,
           "lo_s": lo, "hi_s": hi_s}
    derived = PS.zero_s(ctx)
    assert derived == pytest.approx(zero, abs=1e-4)
    prog = [p for p in PS.on_trace_clock(PS.in_span(ctx), derived)
            if p[0] == "serve.infer_wave"]
    # the ops; the CPU thread pool's region markers fall anywhere
    ops = [ev for ev in dev if "hlo_op" in ev[3]]
    assert len(prog) == 3 and ops, layout
    for name, s, e, _ in ops:
        assert any(ps - tol <= s and e <= pe + tol
                   for _, ps, pe in prog), (name, s, e, prog)


def test_scope_reduction_on_a_served_wave(tmp_path):
    """Every scope of a mixed wave with capture has device time in a CPU
    trace of the wave."""
    from repro.configs.vitdet_l import SIM
    from repro.core import vit_backbone as vb
    from repro.core.partition import LOW, RegionPlan
    from repro.models import registry
    from repro.offload.simulator import ServerModel
    from repro.serve.request import FeatureCache
    params = registry.init_params(SIM, jax.random.PRNGKey(0))
    part = vb.vit_partition(SIM)
    sm = ServerModel(SIM, params, top_k=8, score_thresh=0.0,
                     b_buckets=(1,))
    st = np.zeros((part.n_regions,), np.int8)
    st[:4] = LOW
    size = SIM.vit.img_size[0]
    frame = np.random.default_rng(0).uniform(
        0, 1, (1, size, size, 3)).astype(np.float32)

    def serve():
        return sm.infer_wave(frame, [RegionPlan(st)], beta=2,
                             caches=[FeatureCache(part.n_regions)],
                             frame_ids=[0], capture_beta=2)
    serve()
    jax.profiler.start_trace(str(tmp_path))
    serve()
    jax.profiler.stop_trace()
    dev, _, _ = TR.read(TR.latest_xplane(str(tmp_path)),
                        device_line="tf_XLA", device_plane="/host:CPU")
    scoped = PS.op_scopes(dev, [])
    lo, hi = min(e[1] for e in dev), max(e[2] for e in dev)
    for top in spans.SCOPES:
        assert PS.scope_ns(scoped, top, lo, hi) > 0, top
    assert {sc for sc, _, _ in scoped} >= {"vit.post_beta/restore",
                                           "vit.pre_beta/block00"}
