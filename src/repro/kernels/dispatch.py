"""Kernel backend dispatch layer — routes model hot paths to the Pallas
kernels or the pure-jnp (XLA) reference implementations.

Every dispatch site takes a ``backend`` argument:

  ``"xla"``     pure-jnp path (the original model code) — always available.
  ``"pallas"``  the validated Pallas kernels under ``repro.kernels``;
                off-TPU they run in *interpret* mode (the ops.py
                convention), which is numerically exact but slow — meant
                for parity testing, not performance.
  ``"auto"``    ``"pallas"`` on TPU, ``"xla"`` everywhere else.  Interpret
                mode is a correctness tool, so auto never selects it for
                the hot path.
  ``None``      the process default (``set_backend``), else ``"auto"``.
                The kernels define custom VJPs (window/flash attention
                analytic backward, pooling closed forms), so the bare
                default no longer has to force XLA for gradient safety:
                training code that never mentions a backend rides the
                Pallas lane on TPU and XLA elsewhere.

Backend resolution is a hot-path operation (every attention call in a
traced forward hits it), so the ``REPRO_BACKEND`` environment variable
is read ONCE at import and cached; it still overrides everything —
useful for A/B runs of the benchmark harness without touching call
sites.  Tests that monkeypatch the env var must call
``refresh_from_env()`` afterwards.  Precedence, strongest first:

  env var (cached) > per-call ``backend`` arg > ``set_backend()`` > auto

Only the *shapes the kernels support* are routed to Pallas; anything
else (query offsets, causal or cross-length ``kv_len`` masks) stays on
the XLA path — the dispatcher is a router, not a second implementation.
"""
from __future__ import annotations

import contextlib
import os
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.decode_attention import ops as _decode
from repro.kernels.flash_attention import ops as _flash
from repro.kernels.fused_serving import ops as _fused
from repro.kernels.int8_matmul import ops as _int8
from repro.kernels.int8_matmul import ref as _int8_ref
from repro.kernels.mixed_res_pool import ops as _pool
from repro.kernels.window_attention import ops as _win

BACKENDS = ("auto", "pallas", "xla")
ENV_VAR = "REPRO_BACKEND"

QUANT_MODES = ("native", "dequant")
QUANT_ENV_VAR = "REPRO_QUANT"

_ENV_BACKEND: Optional[str] = None      # cached env override ('' -> None)
_PROCESS_BACKEND: Optional[str] = None  # set_backend() default
_ENV_QUANT: Optional[str] = None        # cached REPRO_QUANT override
_PROCESS_QUANT: Optional[str] = None    # set_quant_mode() default


def _check(backend: str) -> str:
    if backend not in BACKENDS:
        raise ValueError(f"backend must be one of {BACKENDS}, got "
                         f"{backend!r}")
    return backend


def _check_quant(mode: str) -> str:
    if mode not in QUANT_MODES:
        raise ValueError(f"quant mode must be one of {QUANT_MODES}, got "
                         f"{mode!r}")
    return mode


def refresh_from_env() -> Optional[str]:
    """Re-read the cached env overrides — ``REPRO_BACKEND`` and
    ``REPRO_QUANT`` together (tests that monkeypatch either env).  Both
    are resolved on hot paths (every attention / quantized-matmul call
    in a traced forward), so neither is consulted per call."""
    global _ENV_BACKEND, _ENV_QUANT
    env = os.environ.get(ENV_VAR)
    _ENV_BACKEND = _check(env) if env else None
    qenv = os.environ.get(QUANT_ENV_VAR)
    _ENV_QUANT = _check_quant(qenv) if qenv else None
    return _ENV_BACKEND


def set_backend(backend: Optional[str]) -> None:
    """Set the process-wide default used when a call site passes
    ``backend=None``.  ``None`` restores the built-in ``"auto"``."""
    global _PROCESS_BACKEND
    _PROCESS_BACKEND = _check(backend) if backend is not None else None


def get_backend() -> Optional[str]:
    """The current process default (None when unset)."""
    return _PROCESS_BACKEND


@contextlib.contextmanager
def backend_scope(backend: Optional[str]):
    """Temporarily set the process default (trace-time scoping: wrap a
    jit trace to pin every ``backend=None`` site inside it).  A ``None``
    scope is a no-op — the current default stays in force."""
    if backend is None:
        yield
        return
    prev = _PROCESS_BACKEND
    set_backend(backend)
    try:
        yield
    finally:
        set_backend(prev)


def resolve(backend: Optional[str] = None) -> str:
    """Resolve a backend request to a concrete {"pallas", "xla"} choice."""
    if _ENV_BACKEND is not None:
        backend = _ENV_BACKEND
    elif backend is None:
        backend = _PROCESS_BACKEND if _PROCESS_BACKEND is not None else "auto"
    _check(backend)
    if backend == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "xla"
    return backend


def use_pallas(backend: Optional[str] = None) -> bool:
    return resolve(backend) == "pallas"


# ---------------------------------------------------------------------------
# quantized-execution mode — mirrors the backend machinery exactly.
#
#   "native"   QuantTensor matmuls run the int8 x int8 -> int32 lane
#              (Pallas kernel on the pallas backend, the
#              ``preferred_element_type=int32`` dot_general elsewhere)
#              with dynamic per-row activation quantization.
#   "dequant"  weights are dequantized to float and the plain GEMM runs
#              — the numerically-transparent oracle the parity tests
#              compare the native lane against.
#
# Precedence, strongest first (same contract as backends):
#
#   env REPRO_QUANT (cached) > per-call ``mode`` arg > set_quant_mode()
#   > "native"


def set_quant_mode(mode: Optional[str]) -> None:
    """Process-wide default quant mode for ``mode=None`` call sites.
    ``None`` restores the built-in ``"native"``."""
    global _PROCESS_QUANT
    _PROCESS_QUANT = _check_quant(mode) if mode is not None else None


def get_quant_mode() -> Optional[str]:
    """The current process default (None when unset)."""
    return _PROCESS_QUANT


@contextlib.contextmanager
def quant_scope(mode: Optional[str]):
    """Temporarily set the process quant mode (trace-time scoping, the
    ``backend_scope`` twin).  A ``None`` scope is a no-op."""
    if mode is None:
        yield
        return
    prev = _PROCESS_QUANT
    set_quant_mode(mode)
    try:
        yield
    finally:
        set_quant_mode(prev)


def resolve_quant(mode: Optional[str] = None) -> str:
    """Resolve a quant-mode request to {"native", "dequant"}."""
    if _ENV_QUANT is not None:
        return _ENV_QUANT
    if mode is None:
        return _PROCESS_QUANT if _PROCESS_QUANT is not None else "native"
    return _check_quant(mode)


refresh_from_env()


# ---------------------------------------------------------------------------
# thin wrappers over the kernel entry points (ops.py handles padding,
# layout, autotuned block sizes and interpret-mode selection; nothing to
# add here but a stable import point that models/ can use without
# reaching into each kernel).


def window_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                     window: int,
                     win_valid: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Pallas non-overlapping window attention (ViTDet window blocks).

    q: (B, T, H, Dh); k/v: (B, T, KV, Dh); T % window == 0.
    ``win_valid``: optional (B,) valid-window counts — pad windows of a
    length-bucketed sequence emit zeros.  Differentiable (custom VJP).
    """
    return _win.window_attention(q, k, v, window, win_valid=win_valid)


def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    causal: bool = False,
                    kv_len: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Pallas flash attention (ViTDet global blocks / LM prefill).

    q: (B, T, H, Dh); k/v: (B, S, KV, Dh).  ``kv_len``: optional (B,)
    valid lengths of a padded non-causal self-attention (T == S); query
    rows past a row's length come out zero.  Differentiable (custom VJP).
    """
    return _flash.flash_attention(q, k, v, causal=causal, kv_len=kv_len)


def decode_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                     kv_len: jnp.ndarray) -> jnp.ndarray:
    """Pallas one-token GQA decode against the KV cache.

    q: (B, 1, H, Dh); k/v: (B, S, KV, Dh); kv_len: (B,) valid lengths.
    """
    return _decode.decode_attention(q, k, v, kv_len)


def avg_pool(x: jnp.ndarray, d: int) -> jnp.ndarray:
    """Pallas average pool — drop-in for mixed_res.downsample_grid."""
    return _pool.avg_pool_2d(x, d)


def nn_upsample(x: jnp.ndarray, d: int) -> jnp.ndarray:
    """Pallas nearest-neighbour upsample (restoration)."""
    return _pool.nn_upsample_2d(x, d)


def fused_pack_pos(bank: jnp.ndarray, pos_bank: jnp.ndarray,
                   win_src: jnp.ndarray, nw: jnp.ndarray) -> jnp.ndarray:
    """Fused serving prologue: window-bank gather + positional-embedding
    add + pad-window zeroing in one kernel (no HBM round-trip between
    pack and pos-embed).  Returns packed tokens (B, nw_pad * w2, C)."""
    return _fused.fused_pack_pos(bank, pos_bank, win_src, nw)


def fused_restore(windows: jnp.ndarray, out_src: jnp.ndarray,
                  out_map: jnp.ndarray, window: int, downsample: int,
                  reuse_tiles: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Fused serving epilogue: destination-major restoration gather
    (window un-pack + low-res upsample + reuse-tile splice) in one
    kernel.  ``windows``: packed activations (B, nw_pad, w2, D)."""
    return _fused.fused_restore(windows, out_src, out_map, window,
                                downsample, reuse_tiles=reuse_tiles)


def int8_matmul(xq: jnp.ndarray, wq: jnp.ndarray, sx: jnp.ndarray,
                sw: jnp.ndarray, *, out_dtype=jnp.float32,
                backend: Optional[str] = None) -> jnp.ndarray:
    """Quantized GEMM for the int8 weight lane (repro.quant.qtensor).

    xq: (M, K) int8 row-quantized activations; wq: (K, N) int8
    per-output-channel weights; sx: (M,) / sw: (N,) f32 scales.  The
    pallas backend runs the blocked int8 x int8 -> int32 kernel
    (kernels/int8_matmul, autotuned per-dtype block sizes); the XLA
    path is the ``dot_general(..., preferred_element_type=int32)``
    reference — bit-exact against the kernel.
    """
    if use_pallas(backend):
        return _int8.int8_matmul(xq, wq, sx, sw, out_dtype=out_dtype)
    return _int8_ref.int8_matmul_ref(xq, wq, sx, sw, out_dtype=out_dtype)
