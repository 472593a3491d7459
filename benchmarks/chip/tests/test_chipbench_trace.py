"""The trace reduction: busy union, idle gaps named by host spans,
kernel time by name, on hand-made events and on a trace recorded on the
CPU."""
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
from chipbench import tracing as TR  # noqa: E402


def _ev(name, s, e, **st):
    return (name, float(s), float(e), st)


def test_busy_gaps_and_names():
    dev = [_ev("fusion.1", 10, 30),
           _ev("%_flash_attention.2 = f32[1,16,4096,64]{3,2,1,0} "
               "custom-call(f32[1,16,4096,64] %bitcast.1)", 20, 40),
           _ev("fusion.3", 60, 70), _ev("fusion.4", 95, 130)]
    host = [_ev("chipbench.wait", 0, 100), _ev("chipbench.stage", 42, 58),
            _ev("chipbench.idle", 72, 90)]
    assert TR.busy_ns(dev, 0, 100) == 30 + 10 + 5
    assert TR.gaps(dev, 0, 100) == [(0, 10), (40, 60), (70, 95)]
    named = TR.named_gaps(dev, host, 0, 100)
    assert named[0] == ["chipbench.idle", pytest.approx(25e-9)]
    assert named[1] == ["chipbench.stage", pytest.approx(20e-9)]
    assert named[2] == ["chipbench.wait", pytest.approx(10e-9)]
    assert TR.kernel_events(dev, "_flash_attention", 0, 100) == [dev[1]]
    # an op that only consumes the kernel's output is not the kernel
    dev.append(_ev("%fusion.9 = f32[4] fusion(f32[4] %_flash_attention.2)",
                   45, 50))
    assert TR.kernel_events(dev, "_flash_attention", 0, 100) == [dev[1]]
    # nor is a call that the span cuts
    assert TR.kernel_events(dev, "_flash_attention", 25, 100) == []
    top = TR.top_ops(dev, 0, 100)
    assert top[0][0] == "fusion.1" and top[0][1] == pytest.approx(20e-9)
    assert top[1][0] == "_flash_attention.2 f32[1,16,4096,64]{3,2,1,0}"


def test_reduction_on_a_cpu_trace(tmp_path):
    import jax
    import jax.numpy as jnp
    x = jnp.ones((256, 256))
    f = jax.jit(lambda a: (a @ a).sum())
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    # one outer span, so that every gap between the inner ones has a name
    with jax.profiler.TraceAnnotation("chipbench.loop"):
        for i in range(3):
            with jax.profiler.TraceAnnotation("chipbench.wait", wave=i):
                f(x).block_until_ready()
        with jax.profiler.TraceAnnotation("chipbench.idle"):
            pass
    jax.profiler.stop_trace()
    path = TR.latest_xplane(str(tmp_path))
    assert path is not None
    dev, host, layout = TR.read(path, device_line="tf_XLA",
                                device_plane="/host:CPU")
    waits = [h for h in host if h[0] == "chipbench.wait"]
    assert [h[3].get("wave") for h in waits] == [0, 1, 2]
    assert any(h[0] == "chipbench.idle" for h in host)
    assert dev, layout
    lo = min(h[1] for h in host)
    hi = max(h[2] for h in host)
    busy = TR.busy_ns(dev, lo, hi)
    idle = sum(e - s for s, e in TR.gaps(dev, lo, hi))
    assert busy > 0 and busy + idle == pytest.approx(hi - lo)
    assert all(name.startswith("chipbench.") for name, _ in
               TR.named_gaps(dev, host, lo, hi))


def test_logged_spans_on_the_trace_clock(tmp_path):
    """The loop's spans, logged on the host clock and rebased to the
    start of the trace, enclose the ops they ran (on the CPU the ops are
    host events, so the host tracer stays on here)."""
    import time

    import jax
    import jax.numpy as jnp
    from chipbench.harness import span
    x = jnp.ones((512, 512))
    f = jax.jit(lambda a: (a @ a @ a).sum())
    f(x).block_until_ready()
    log = []
    zero = time.perf_counter()
    jax.profiler.start_trace(str(tmp_path))
    # the trace's zero lies somewhere inside start_trace
    tol = (time.perf_counter() - zero) * 1e9 + 1e6
    for i in range(3):
        with span(log, "wait", wave=i):
            f(x).block_until_ready()
        time.sleep(0.02)
    hi = (time.perf_counter() - zero) * 1e9
    jax.profiler.stop_trace()
    dev, _, layout = TR.read(TR.latest_xplane(str(tmp_path)),
                             device_line="tf_XLA", device_plane="/host:CPU")
    host = TR.rebase(log, zero, 0.0, hi)
    assert [h[3]["wave"] for h in host] == [0, 1, 2]
    assert dev, layout
    # every op lies inside one of the logged spans
    for name, s, e, _ in dev:
        assert any(hs - tol <= s and e <= he + tol
                   for _, hs, he, _ in host), (name, s, e, host)


def test_every_per_layer_reader_by_name():
    """Each per-layer metric of BENCHMARK.json has a reader found by its
    name, which reads a hand-made traced span."""
    import json
    from types import SimpleNamespace as NS

    from chipbench import flops as F
    from chipbench import harness as H
    bench = json.loads((H.ROOT / "BENCHMARK.json").read_text())
    model = json.loads((H.ROOT / bench["configs"][0]["file"]).read_text())
    model = model["model"]
    off = NS(n_low=4, n_reuse=2)
    jobs = [NS(off=off, due=0.1 * i, dispatch=0.1 * i + 0.01,
               done=0.1 * i + 0.06) for i in range(10)]
    waves = [(i, 1, 24, 0.004, 0.1 * i + 0.01, 0.1 * i + 0.06)
             for i in range(10)]
    flash = ("%_flash_attention.3 = f32[1,16,4096,64]{3,2,1,0} "
             "custom-call(f32[1,16,4096,64] %x)")
    dev = [_ev(flash, 0.1e9 * i + 0.02e9, 0.1e9 * i + 0.05e9)
           for i in range(10)]
    ctx = {"jobs": jobs, "waves": waves, "lo_s": 0.0, "hi_s": 1.0,
           "device_events": dev, "host_spans": [], "trace_lo_ns": 0.0,
           "trace_hi_ns": 1e9, "model": model, "beta": 2,
           "device_kind": "TPU v5 lite", "traffic": {}}
    got = {m["name"]: H.load_reader(m["name"])(ctx)
           for m in bench["per_layer"]}
    assert got["device_idle_frac"] == pytest.approx(70.0)
    assert got["wave_size_mean"] == 1.0
    assert got["queue_wait_ms_p50"] == pytest.approx(10.0)
    assert got["grid_host_ms_per_wave"] == pytest.approx(4.0)
    work = 10 * F.frame_flops(model, 4, 2, 2)
    assert got["backbone_mfu"] == pytest.approx(
        100 * work / (0.3 * F.peaks("TPU v5 lite")["bf16_flops"]))
    assert 0 < got["flash_attn_roofline"] <= 100
    assert got["window_attn_roofline"] is None        # no window calls
