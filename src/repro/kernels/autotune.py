"""Block-size autotuner for the Pallas serving kernels.

The kernels' tile sizes (flash ``bq``/``bk``, window-attention ``wb``,
decode ``bs``) are fixed defaults chosen for one TPU generation; the
right values differ per device kind and per shape regime.  This module
sweeps a small candidate grid at ``warmup()`` time, times each candidate
on the device, and caches the winner on disk keyed
``(device kind, kernel, shape bucket)`` so later processes skip the
sweep entirely.

Knobs:

  ``REPRO_AUTOTUNE=0``       disable — every lookup returns the fixed
                             default (the escape hatch for CI or when a
                             stale cache misbehaves).
  ``REPRO_AUTOTUNE_CACHE``   override the cache directory
                             (default ``~/.cache/repro/autotune``).

Shape buckets round every dynamic dimension up to a power of two so the
cache stays bounded; a lookup miss always falls back to the kernel's
fixed default, never to a sweep in the hot path — sweeps only run from
the explicit ``tune_*`` entry points called by warmup.

Off-TPU the kernels run in interpret mode, where candidate timings are
meaningless; sweeps are skipped there unless ``force=True`` (unit tests
exercise the machinery that way).
"""
from __future__ import annotations

import json
import os
import re
import threading
import time
import warnings
from pathlib import Path
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

ENV_VAR = "REPRO_AUTOTUNE"
CACHE_ENV = "REPRO_AUTOTUNE_CACHE"

# candidate grids per kernel; first entry is never assumed — the fixed
# ops.py default is always a candidate so tuning can only tie or win.
FLASH_CANDIDATES = ({"bq": 128, "bk": 128}, {"bq": 128, "bk": 256},
                    {"bq": 256, "bk": 256}, {"bq": 256, "bk": 512},
                    {"bq": 512, "bk": 512})
# window blocks stay whole sublane tiles (window_attention.ops.resolve_wb)
WINDOW_CANDIDATES = ({"wb": 8}, {"wb": 16}, {"wb": 32})
DECODE_CANDIDATES = ({"bs": 256}, {"bs": 512}, {"bs": 1024})
MATMUL_CANDIDATES = ({"bm": 128, "bn": 128, "bk": 128},
                     {"bm": 256, "bn": 128, "bk": 128},
                     {"bm": 128, "bn": 256, "bk": 256},
                     {"bm": 256, "bn": 256, "bk": 256},
                     {"bm": 512, "bn": 256, "bk": 256})

_LOCK = threading.Lock()
_TABLE: Dict[str, Dict[str, Dict]] = {}     # kernel -> bucket_key -> entry
_LOADED_FOR: Optional[str] = None           # device kind the table is for

_ENABLED: bool = os.environ.get(ENV_VAR, "1") != "0"


def refresh_from_env() -> bool:
    """Re-read ``REPRO_AUTOTUNE`` (tests that monkeypatch the env).

    ``enabled()`` sits on the kernel-dispatch hot path (every ops.py
    block-size resolution calls ``lookup``), so the env var is read once
    at import and cached — same convention as kernels.dispatch."""
    global _ENABLED
    _ENABLED = os.environ.get(ENV_VAR, "1") != "0"
    return _ENABLED


def enabled() -> bool:
    return _ENABLED


def device_kind() -> str:
    kind = jax.devices()[0].device_kind
    return re.sub(r"[^A-Za-z0-9._-]+", "_", kind)


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def cache_dir() -> Path:
    env = os.environ.get(CACHE_ENV)
    if env:
        return Path(env)
    return Path.home() / ".cache" / "repro" / "autotune"


def cache_path(kind: Optional[str] = None) -> Path:
    return cache_dir() / f"{kind or device_kind()}.json"


def bucket_key(**dims) -> str:
    """Canonical bucket string: dims sorted by name, dynamic sizes
    rounded up to the next power of two."""
    parts = []
    for name in sorted(dims):
        val = dims[name]
        if isinstance(val, (int,)) and not isinstance(val, bool):
            val = _pow2(val)
        parts.append(f"{name}={val}")
    return ",".join(parts)


def _pow2(n: int) -> int:
    p = 1
    while p < max(1, n):
        p *= 2
    return p


# ---------------------------------------------------------------------------
# cache table


def _load(kind: str) -> None:
    global _LOADED_FOR
    if _LOADED_FOR == kind:
        return
    _TABLE.clear()
    path = cache_path(kind)
    try:
        _TABLE.update(json.loads(path.read_text()))
    except (OSError, ValueError):
        pass
    _LOADED_FOR = kind


def _save(kind: str) -> None:
    path = cache_path(kind)
    try:
        path.parent.mkdir(parents=True, exist_ok=True)
        tmp = path.with_suffix(".tmp")
        tmp.write_text(json.dumps(_TABLE, indent=1, sort_keys=True))
        tmp.replace(path)
    except OSError:
        pass                            # cache is best-effort


def clear_memory_cache() -> None:
    """Drop the in-process table (tests; the disk file is untouched)."""
    global _LOADED_FOR
    with _LOCK:
        _TABLE.clear()
        _LOADED_FOR = None


def lookup(kernel: str, bucket: str) -> Optional[Dict]:
    """Tuned params for (kernel, bucket) or None.  Never sweeps."""
    if not enabled():
        return None
    with _LOCK:
        _load(device_kind())
        entry = _TABLE.get(kernel, {}).get(bucket)
    return dict(entry["params"]) if entry else None


def block(kernel: str, bucket: str, default: Dict) -> Dict:
    """Resolved block params: tuned winner if cached, else ``default``."""
    tuned = lookup(kernel, bucket)
    out = dict(default)
    if tuned:
        out.update({k: v for k, v in tuned.items() if k in out})
    return out


def record(kernel: str, bucket: str, params: Dict, us: float) -> None:
    with _LOCK:
        kind = device_kind()
        _load(kind)
        _TABLE.setdefault(kernel, {})[bucket] = {
            "params": dict(params), "us": float(us)}
        _save(kind)


# ---------------------------------------------------------------------------
# sweeping


def _time_us(fn: Callable[[], jnp.ndarray], reps: int = 3) -> float:
    fn().block_until_ready()            # compile + warm
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        fn().block_until_ready()
        best = min(best, (time.perf_counter() - t0) * 1e6)
    return best


def tune(kernel: str, bucket: str, candidates: Sequence[Dict],
         bench: Callable[[Dict], Optional[Callable[[], jnp.ndarray]]], *,
         default: Optional[Dict] = None, force: bool = False,
         reps: int = 3) -> Optional[Dict]:
    """Sweep ``candidates`` for (kernel, bucket); cache and return the
    winner.  ``bench(params)`` returns a nullary callable running the
    kernel with those params, or None when the candidate is invalid for
    the shape.  A candidate that fails to lower or run is reported as a
    ``RuntimeWarning`` with its error and left out; when it is
    ``default`` — the block the kernel runs untuned — the sweep raises,
    because the served executables would fail the same way.  Returns the
    cached/tuned params, or None when tuning is disabled or skipped
    (off-TPU without force)."""
    if not enabled():
        return None
    cached = lookup(kernel, bucket)
    if cached is not None:
        return cached
    if not (on_tpu() or force):
        return None
    results: List[Tuple[float, Dict]] = []
    for params in candidates:
        fn = bench(dict(params))
        if fn is None:
            continue
        try:
            results.append((_time_us(fn, reps=reps), dict(params)))
        except Exception as e:          # candidate failed to lower/run
            if default is not None and dict(params) == dict(default):
                raise RuntimeError(
                    f"autotune {kernel} [{bucket}]: the kernel default "
                    f"{params} failed") from e
            warnings.warn(
                f"autotune {kernel} [{bucket}]: candidate {params} "
                f"failed: {type(e).__name__}: {str(e)[:500]}",
                RuntimeWarning, stacklevel=2)
    if not results:
        return None
    us, params = min(results, key=lambda r: r[0])
    record(kernel, bucket, params, us)
    return params


# ---------------------------------------------------------------------------
# kernel-specific entry points (called from warmup paths)


def window_bucket(B: int, T: int, H: int, Dh: int, window: int,
                  dtype) -> str:
    return bucket_key(bw=B * (T // window), h=H, dh=Dh, w=window,
                      dt=jnp.dtype(dtype).name)


def flash_bucket(B: int, T: int, S: int, H: int, KV: int, Dh: int,
                 causal: bool, dtype) -> str:
    return bucket_key(b=B, t=T, s=S, h=H, kv=KV, dh=Dh, causal=causal,
                      dt=jnp.dtype(dtype).name)


def decode_bucket(B: int, S: int, H: int, KV: int, Dh: int, dtype) -> str:
    return bucket_key(b=B, s=S, h=H, kv=KV, dh=Dh,
                      dt=jnp.dtype(dtype).name)


def matmul_bucket(M: int, N: int, K: int, act_dtype, weight_dtype) -> str:
    """GEMM bucket keyed on BOTH operand dtypes: the int8 lane and a
    half-precision lane have different MXU tile economics, so an int8
    sweep's winner must never answer an fp32/fp16 lookup (or vice
    versa) — the dtype-separation contract tests/test_autotune pins."""
    return bucket_key(m=M, n=N, k=K, adt=jnp.dtype(act_dtype).name,
                      wdt=jnp.dtype(weight_dtype).name)


def tune_window(B: int, T: int, H: int, Dh: int, window: int, *,
                KV: Optional[int] = None, dtype=jnp.float32,
                force: bool = False) -> Optional[Dict]:
    """Sweep the window block on the flagged (``win_valid``) kernel: the
    bucket serves the padded executables too, and the flagged kernel's
    block rules are the stricter of the two."""
    from repro.kernels.window_attention import kernel as _wk
    from repro.kernels.window_attention import ops as _win
    KV = H if KV is None else KV
    BW = B * (T // window)
    rng = jax.random.PRNGKey(0)
    q = jax.random.normal(rng, (B, T, H, Dh), dtype)
    k = jax.random.normal(rng, (B, T, KV, Dh), dtype)
    v = jax.random.normal(rng, (B, T, KV, Dh), dtype)
    win_valid = jnp.full((B,), T // window, jnp.int32)

    def bench(params):
        wb = params["wb"]
        if _win.resolve_wb(wb, BW) != wb:
            return None
        return lambda: _win.window_attention(q, k, v, window, wb=wb,
                                             win_valid=win_valid)

    return tune("window_attention",
                window_bucket(B, T, H, Dh, window, dtype),
                WINDOW_CANDIDATES, bench,
                default={"wb": _wk.DEFAULT_WB}, force=force)


def tune_flash(B: int, T: int, S: int, H: int, Dh: int, *,
               KV: Optional[int] = None, causal: bool = False,
               dtype=jnp.float32, force: bool = False) -> Optional[Dict]:
    from repro.kernels.flash_attention import kernel as _fk
    from repro.kernels.flash_attention import ops as _flash
    KV = H if KV is None else KV
    rng = jax.random.PRNGKey(0)
    q = jax.random.normal(rng, (B, T, H, Dh), dtype)
    k = jax.random.normal(rng, (B, S, KV, Dh), dtype)
    v = jax.random.normal(rng, (B, S, KV, Dh), dtype)

    def bench(params):
        return lambda: _flash.flash_attention(
            q, k, v, causal=causal, bq=params["bq"], bk=params["bk"])

    return tune("flash_attention",
                flash_bucket(B, T, S, H, KV, Dh, causal, dtype),
                FLASH_CANDIDATES, bench,
                default={"bq": _fk.DEFAULT_BQ, "bk": _fk.DEFAULT_BK},
                force=force)


def tune_decode(B: int, S: int, H: int, Dh: int, *,
                KV: Optional[int] = None, dtype=jnp.float32,
                force: bool = False) -> Optional[Dict]:
    from repro.kernels.decode_attention import kernel as _dk
    from repro.kernels.decode_attention import ops as _dec
    KV = H if KV is None else KV
    rng = jax.random.PRNGKey(0)
    q = jax.random.normal(rng, (B, 1, H, Dh), dtype)
    k = jax.random.normal(rng, (B, S, KV, Dh), dtype)
    v = jax.random.normal(rng, (B, S, KV, Dh), dtype)
    kv_len = jnp.full((B,), S, jnp.int32)

    def bench(params):
        return lambda: _dec.decode_attention(q, k, v, kv_len,
                                             bs=params["bs"])

    return tune("decode_attention", decode_bucket(B, S, H, KV, Dh, dtype),
                DECODE_CANDIDATES, bench,
                default={"bs": _dk.DEFAULT_BS}, force=force)


def tune_matmul(M: int, N: int, K: int, *, out_dtype=jnp.float32,
                force: bool = False) -> Optional[Dict]:
    """Sweep the int8 GEMM block sizes for an (M, N, K) shape bucket."""
    from repro.kernels.int8_matmul import kernel as _mk
    from repro.kernels.int8_matmul import ops as _mm
    rng = np.random.default_rng(0)
    xq = jnp.asarray(rng.integers(-127, 128, (M, K), dtype=np.int8))
    wq = jnp.asarray(rng.integers(-127, 128, (K, N), dtype=np.int8))
    sx = jnp.ones((M,), jnp.float32)
    sw = jnp.ones((N,), jnp.float32)

    def bench(params):
        return lambda: _mm.int8_matmul(xq, wq, sx, sw,
                                       out_dtype=out_dtype,
                                       bm=params["bm"], bn=params["bn"],
                                       bk=params["bk"])

    return tune("int8_matmul",
                matmul_bucket(M, N, K, jnp.int8, jnp.int8),
                MATMUL_CANDIDATES, bench,
                default={"bm": _mk.DEFAULT_BM, "bn": _mk.DEFAULT_BN,
                         "bk": _mk.DEFAULT_BK}, force=force)
