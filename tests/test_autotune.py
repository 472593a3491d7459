"""Block-size autotuner (kernels.autotune): cache machinery, disk
roundtrip, the REPRO_AUTOTUNE=0 escape hatch, and the ops.py default
fallback.  Sweeps run with ``force=True`` (interpret-mode timings are
meaningless but exercise the full machinery)."""
import json

import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import autotune
from repro.kernels.window_attention import kernel as wk
from repro.kernels.window_attention import ops as wops


@pytest.fixture()
def tmp_cache(tmp_path, monkeypatch):
    monkeypatch.setenv(autotune.CACHE_ENV, str(tmp_path))
    autotune.clear_memory_cache()
    yield tmp_path
    autotune.clear_memory_cache()


def test_bucket_key_rounds_to_pow2():
    assert autotune.bucket_key(b=3, h=8) == "b=4,h=8"
    assert autotune.bucket_key(t=100, dt="float32") == "dt=float32,t=128"
    # stable ordering regardless of kwarg order
    assert autotune.bucket_key(b=2, a=1) == autotune.bucket_key(a=1, b=2)


def test_block_falls_back_to_default(tmp_cache):
    out = autotune.block("window_attention", "b=1",
                         {"wb": wk.DEFAULT_WB})
    assert out == {"wb": wk.DEFAULT_WB}


def test_tune_records_and_persists(tmp_cache):
    calls = []

    def bench(params):
        calls.append(params["x"])
        # deterministic fake kernel: candidate 2 is "fastest" only in
        # the sense that all run; min-of-reps picks whichever, we just
        # assert a winner lands in the cache
        return lambda: jnp.zeros((1,))

    won = autotune.tune("fake_kernel", "b=1",
                        ({"x": 1}, {"x": 2}), bench, force=True, reps=1)
    assert won in ({"x": 1}, {"x": 2})
    assert sorted(calls) == [1, 2]
    # in-memory hit
    assert autotune.lookup("fake_kernel", "b=1") == won
    # disk roundtrip: a fresh process (cleared memory) reloads it
    autotune.clear_memory_cache()
    assert autotune.lookup("fake_kernel", "b=1") == won
    data = json.loads(autotune.cache_path().read_text())
    assert data["fake_kernel"]["b=1"]["params"] == won
    # a second tune call is a cache hit: bench never runs again
    calls.clear()
    assert autotune.tune("fake_kernel", "b=1", ({"x": 1}, {"x": 2}),
                         bench, force=True) == won
    assert calls == []


def test_tune_skips_invalid_and_failing_candidates(tmp_cache):
    def bench(params):
        if params["x"] == 1:
            return None                      # invalid for the shape
        if params["x"] == 2:
            def boom():
                raise RuntimeError("lowering failed")
            return boom
        return lambda: jnp.zeros((1,))

    with pytest.warns(RuntimeWarning, match=r"candidate \{'x': 2\} "
                      r"failed: RuntimeError: lowering failed"):
        won = autotune.tune("fake2", "b=1", ({"x": 1}, {"x": 2}, {"x": 3}),
                            bench, force=True, reps=1)
    assert won == {"x": 3}
    # all candidates invalid -> no winner, nothing cached
    assert autotune.tune("fake3", "b=1", ({"x": 1},), bench,
                         force=True) is None
    assert autotune.lookup("fake3", "b=1") is None


def test_tune_raises_when_default_fails(tmp_cache):
    """A failing default is the block every untuned executable would
    run, so the sweep raises instead of reporting and moving on."""
    def bench(params):
        if params["x"] == 1:
            def boom():
                raise RuntimeError("vmem exhausted")
            return boom
        return lambda: jnp.zeros((1,))

    with pytest.raises(RuntimeError, match="kernel default") as info:
        autotune.tune("fake5", "b=1", ({"x": 1}, {"x": 2}), bench,
                      default={"x": 1}, force=True, reps=1)
    assert "vmem exhausted" in str(info.value.__cause__)
    assert autotune.lookup("fake5", "b=1") is None


def test_autotune_disabled_env(tmp_cache, monkeypatch):
    """REPRO_AUTOTUNE is hoisted out of the hot path (read once at
    import, same convention as kernels.dispatch), so monkeypatching the
    env must be followed by refresh_from_env()."""
    autotune.record("fake4", "b=1", {"x": 9}, 1.0)
    monkeypatch.setenv(autotune.ENV_VAR, "0")
    assert autotune.enabled(), "cached: env flip alone must NOT apply"
    autotune.refresh_from_env()
    try:
        assert not autotune.enabled()
        # disabled: lookups miss (defaults win) and sweeps are no-ops
        assert autotune.lookup("fake4", "b=1") is None
        assert autotune.block("fake4", "b=1", {"x": 0}) == {"x": 0}
        assert autotune.tune("fake4", "b=2", ({"x": 1},),
                             lambda p: (lambda: jnp.zeros((1,))),
                             force=True) is None
    finally:
        monkeypatch.delenv(autotune.ENV_VAR)
        autotune.refresh_from_env()
    assert autotune.enabled()


@pytest.mark.parametrize("wb", [1, 4, 8, 16, 32, 64])
def test_resolved_window_block_is_a_sublane_tile(wb):
    """Whatever block is asked for (a tuned winner, a caller's ``wb``),
    the kernel runs a divisor of BW that is a multiple of 8 or all of
    BW — the only blocks the flagged kernel compiles with on the TPU."""
    for c in autotune.WINDOW_CANDIDATES:
        assert c["wb"] % 8 == 0
    for BW in range(1, 161):
        got = wops.resolve_wb(wb, BW)
        assert BW % got == 0
        assert got % 8 == 0 or got == BW, (wb, BW, got)


def test_tune_window_end_to_end(tmp_cache):
    """The real window-attention sweep under force: records a winner the
    ops.py wb=None path then resolves (and the kernel still validates)."""
    won = autotune.tune_window(2, 128, 2, 16, 16, force=True)
    assert won is not None and "wb" in won
    bucket = autotune.window_bucket(2, 128, 2, 16, 16, jnp.float32)
    assert autotune.block("window_attention", bucket,
                          {"wb": wk.DEFAULT_WB})["wb"] == won["wb"]
    # shape-bucketed: a different shape misses and falls back
    other = autotune.window_bucket(4, 2048, 16, 64, 64, jnp.float32)
    assert autotune.block("window_attention", other,
                          {"wb": wk.DEFAULT_WB})["wb"] == wk.DEFAULT_WB
