"""jit'd entry point for the flash attention kernel.

Handles the (B, T, H, Dh) <-> (B, H, T, Dh) layout swap, pads T/S up to
the block size (padded keys are masked in-kernel via the static
``kv_valid`` length, or via the per-row ``kv_len`` where one is given),
and picks interpret mode automatically off-TPU.

The entry point carries a ``jax.custom_vjp``: the forward runs the
Pallas kernel, the backward is the analytic softmax-attention gradient
recomputed densely in plain jnp.  The dense recompute materialises the
(T, S) score matrix per (kv head, group), so it targets training-scale
sequences (the serving path never differentiates); it is exact and
keeps the Pallas lane usable under ``jax.grad``.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import autotune
from repro.kernels.flash_attention import kernel as K

NEG_INF = K.NEG_INF


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _round8(n: int) -> int:
    """Smallest multiple of 8 >= n (sublane granularity)."""
    return max(8, ((n + 7) // 8) * 8)


def _forward(q, k, v, kv_len, causal, scale, bq, bk, interpret):
    B, T, H, Dh = q.shape
    S = k.shape[1]

    bq_ = min(bq, _round8(T))
    bk_ = min(bk, _round8(S))
    pad_t = (-T) % bq_
    pad_s = (-S) % bk_

    qt = jnp.moveaxis(q, 2, 1)                       # (B, H, T, Dh)
    kt = jnp.moveaxis(k, 2, 1)
    vt = jnp.moveaxis(v, 2, 1)
    if pad_t:
        qt = jnp.pad(qt, ((0, 0), (0, 0), (0, pad_t), (0, 0)))
    if pad_s:
        kt = jnp.pad(kt, ((0, 0), (0, 0), (0, pad_s), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, 0), (0, pad_s), (0, 0)))

    if kv_len is not None:
        kv_len = jnp.minimum(kv_len.astype(jnp.int32), S)
    out = K.flash_attention_kernel(qt, kt, vt, kv_len, causal=causal,
                                   scale=scale, bq=bq_, bk=bk_, kv_valid=S,
                                   interpret=interpret)
    out = out[:, :, :T, :]
    return jnp.moveaxis(out, 1, 2)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _flash_attention(q, k, v, kv_len, causal, scale, bq, bk, interpret):
    return _forward(q, k, v, kv_len, causal, scale, bq, bk, interpret)


def _vjp_fwd(q, k, v, kv_len, causal, scale, bq, bk, interpret):
    return (_forward(q, k, v, kv_len, causal, scale, bq, bk, interpret),
            (q, k, v, kv_len))


def _vjp_bwd(causal, scale, bq, bk, interpret, res, g):
    """Dense analytic backward (recomputes p; O(T*S) scores).  With
    ``kv_len`` the same masks as the forward: keys past a row's length
    get no probability, and its zeroed query rows pass no gradient."""
    q, k, v, kv_len = res
    B, T, H, Dh = q.shape
    S, KV = k.shape[1], k.shape[2]
    G = H // KV
    f32 = jnp.float32
    qg = q.reshape(B, T, KV, G, Dh).astype(f32)
    kk = k.astype(f32)
    vv = v.astype(f32)
    gg = g.reshape(B, T, KV, G, Dh).astype(f32)
    s = jnp.einsum("btkgd,bskd->bkgts", qg, kk) * scale
    if causal:
        mask = jnp.arange(T)[:, None] >= jnp.arange(S)[None, :]
        s = jnp.where(mask, s, NEG_INF)
    if kv_len is not None:
        valid = jnp.arange(S)[None, :] < kv_len[:, None]        # (B, S)
        s = jnp.where(valid[:, None, None, None], s, NEG_INF)
        gg = jnp.where(valid[:, :, None, None, None], gg, 0.0)
    p = jax.nn.softmax(s, axis=-1)
    dv = jnp.einsum("bkgts,btkgd->bskd", p, gg)
    dp = jnp.einsum("btkgd,bskd->bkgts", gg, vv)
    ds = p * (dp - jnp.sum(dp * p, axis=-1, keepdims=True))
    dq = jnp.einsum("bkgts,bskd->btkgd", ds, kk) * scale
    dk = jnp.einsum("bkgts,btkgd->bskd", ds, qg) * scale
    dq = dq.reshape(B, T, H, Dh).astype(q.dtype)
    return dq, dk.astype(k.dtype), dv.astype(v.dtype), None


_flash_attention.defvjp(_vjp_fwd, _vjp_bwd)

_entry = jax.jit(_flash_attention, static_argnums=(4, 5, 6, 7, 8))


def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    causal: bool = False, scale: Optional[float] = None,
                    kv_len: Optional[jnp.ndarray] = None,
                    bq: Optional[int] = None, bk: Optional[int] = None,
                    interpret: Optional[bool] = None) -> jnp.ndarray:
    """Drop-in for models.attention.sdpa (training/prefill path).

    q: (B, T, H, Dh); k/v: (B, S, KV, Dh).  Returns (B, T, H, Dh).
    ``kv_len``: optional (B,) valid lengths of a padded non-causal
    self-attention (T == S): row b attends to its first kv_len[b] keys,
    and its query rows from kv_len[b] on come out zero.
    ``bq``/``bk`` default to the autotuned block sizes for this shape
    bucket, else to ``kernel.default_blocks`` of the shape.
    Differentiable.
    """
    if interpret is None:
        interpret = not _on_tpu()
    B, T, H, Dh = q.shape
    S, KV = k.shape[1], k.shape[2]
    if kv_len is not None and (causal or T != S):
        raise ValueError("kv_len masks a non-causal self-attention "
                         f"(T == S); got causal={causal}, T={T}, S={S}")
    scale = float(Dh ** -0.5 if scale is None else scale)

    if bq is None or bk is None:
        dq, dk = K.default_blocks(T, S, causal)
        tuned = autotune.block(
            "flash_attention",
            autotune.flash_bucket(B, T, S, H, KV, Dh, causal, q.dtype),
            {"bq": dq, "bk": dk})
        bq = tuned["bq"] if bq is None else bq
        bk = tuned["bk"] if bk is None else bk

    return _entry(q, k, v, kv_len, bool(causal), scale, int(bq), int(bk),
                  bool(interpret))
