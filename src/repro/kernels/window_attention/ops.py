"""jit'd entry point for the window attention kernel.

Reshapes the window-blocked (B, T, H, Dh) stream into per-window blocks,
pads w^2 to the sublane granularity and the window count to WB, and
dispatches the Pallas kernel (interpret mode off-TPU).

The entry point carries a ``jax.custom_vjp``: the forward runs the
Pallas kernel, the backward is the analytic softmax-attention gradient
recomputed in plain jnp per window (windows are tiny — w^2 x w^2 scores
— so the O(w^4) recompute is cheap and exact).  This keeps the Pallas
lane differentiable, so ``dispatch.resolve(None)`` no longer forces XLA
for gradient safety.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import autotune
from repro.kernels.window_attention import kernel as K


def _forward(q, k, v, valid_f, window, scale, wb, interpret):
    B, T, H, Dh = q.shape
    KV = k.shape[2]
    W = T // window

    w2p = ((window + 7) // 8) * 8

    def to_blocks(x, heads):
        x = x.reshape(B * W, window, heads, Dh)
        x = jnp.moveaxis(x, 2, 1)                    # (BW, heads, w2, Dh)
        if w2p != window:
            x = jnp.pad(x, ((0, 0), (0, 0), (0, w2p - window), (0, 0)))
        return x

    flags = None
    if valid_f is not None:
        flags = (jnp.arange(W)[None, :] < valid_f[:, None]) \
            .astype(jnp.int32).reshape(B * W, 1)
    out = K.window_attention_kernel(
        to_blocks(q, H), to_blocks(k, KV), to_blocks(v, KV),
        scale=scale, w2_valid=window, wb=wb, interpret=interpret,
        win_flags=flags)
    out = jnp.moveaxis(out[:, :, :window, :], 1, 2)  # (BW, w2, H, Dh)
    return out.reshape(B, T, H, Dh)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _window_attention(q, k, v, valid_f, window, scale, wb, interpret):
    return _forward(q, k, v, valid_f, window, scale, wb, interpret)


def _vjp_fwd(q, k, v, valid_f, window, scale, wb, interpret):
    out = _forward(q, k, v, valid_f, window, scale, wb, interpret)
    return out, (q, k, v, valid_f)


def _vjp_bwd(window, scale, wb, interpret, res, g):
    """Analytic per-window softmax-attention backward (pure jnp).

    dv = p^T g;  dp = g v^T;  ds = p * (dp - sum_s(dp * p));
    dq = ds k * scale;  dk = ds^T q * scale.  Pad windows (beyond
    ``valid_f``) emit constant zeros in the forward, so their output
    cotangent is masked off before the recompute.
    """
    q, k, v, valid_f = res
    B, T, H, Dh = q.shape
    KV = k.shape[2]
    G = H // KV
    W = T // window
    f32 = jnp.float32
    qw = q.reshape(B, W, window, KV, G, Dh).astype(f32)
    kw = k.reshape(B, W, window, KV, Dh).astype(f32)
    vw = v.reshape(B, W, window, KV, Dh).astype(f32)
    gw = g.reshape(B, W, window, KV, G, Dh).astype(f32)
    if valid_f is not None:
        keep = (jnp.arange(W)[None, :] < valid_f[:, None]).astype(f32)
        gw = gw * keep[:, :, None, None, None, None]
    s = jnp.einsum("bwtkgd,bwskd->bwkgts", qw, kw) * scale
    p = jax.nn.softmax(s, axis=-1)
    dv = jnp.einsum("bwkgts,bwtkgd->bwskd", p, gw)
    dp = jnp.einsum("bwtkgd,bwskd->bwkgts", gw, vw)
    ds = p * (dp - jnp.sum(dp * p, axis=-1, keepdims=True))
    dq = jnp.einsum("bwkgts,bwskd->bwtkgd", ds, kw) * scale
    dk = jnp.einsum("bwkgts,bwtkgd->bwskd", ds, qw) * scale
    dq = dq.reshape(B, T, H, Dh).astype(q.dtype)
    dk = dk.reshape(B, T, KV, Dh).astype(k.dtype)
    dv = dv.reshape(B, T, KV, Dh).astype(v.dtype)
    dvalid = None if valid_f is None else jnp.zeros_like(valid_f)
    return dq, dk, dv, dvalid


_window_attention.defvjp(_vjp_fwd, _vjp_bwd)

_entry = jax.jit(_window_attention, static_argnums=(4, 5, 6, 7))


def resolve_wb(wb: int, BW: int) -> int:
    """The window block the kernel runs for ``BW`` windows when ``wb`` is
    asked for: the largest multiple of 8 that divides BW and is at most
    max(wb, 8), else all of BW.  The flagged kernel's (wb, 1) flags block
    needs a whole 8-row sublane tile or the full axis, so no other block
    compiles on the TPU."""
    want = max(8, min(int(wb), BW))
    for cand in range(want - want % 8, 0, -8):
        if BW % cand == 0:
            return cand
    return BW


def window_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                     window: int, *, scale: Optional[float] = None,
                     win_valid: Optional[jnp.ndarray] = None,
                     wb: Optional[int] = None,
                     interpret: Optional[bool] = None) -> jnp.ndarray:
    """Drop-in for models.attention.window_sdpa.

    q: (B, T, H, Dh); k/v: (B, T, KV, Dh); T % window == 0.
    ``win_valid``: optional (B,) i32 valid-window counts (length-bucketed
    padded sequences) — pad windows' outputs are zeroed in-kernel.
    ``wb=None`` picks the autotuned window-block tiling for this shape
    bucket (kernel default when untuned).  Differentiable.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    B, T, H, Dh = q.shape
    W = T // window
    scale = float(Dh ** -0.5 if scale is None else scale)

    if wb is None:
        wb = autotune.block(
            "window_attention",
            autotune.window_bucket(B, T, H, Dh, window, q.dtype),
            {"wb": K.DEFAULT_WB})["wb"]
    wb = resolve_wb(wb, B * W)

    valid_f = None
    if win_valid is not None:
        # float32 so the custom-VJP boundary has a float cotangent
        # (int primals would need float0 plumbing)
        valid_f = jnp.asarray(win_valid).astype(jnp.float32)
    return _entry(q, k, v, valid_f, window, scale, wb, bool(interpret))
