"""ViTDet window attention Pallas TPU kernel.

The mixed-resolution sequence (core.mixed_res) is *window-blocked*: the
token stream is a concatenation of independent w*w-token windows, and
window attention is dense attention inside each window — the paper's
§III hot path (all but one block per ViTDet subset are window blocks).

Tiling: windows are tiny (w^2 = 64 tokens for w = 8) so a single window
underfills the 128x128 MXU.  The kernel therefore processes ``WB``
windows per program as a batched dot:

    grid = (n_windows / WB, heads)
    q,k,v block (WB, W2p, Dh)   VMEM, W2p = w^2 padded to sublane(8)
    scores      (WB, W2p, W2p)  fp32, formed and consumed in VMEM

With WB = 8, Dh = 64: working set = 3*8*64*64*2B (qkv bf16) + 8*64*64*4B
(scores f32) ~= 1.3 MiB — comfortably inside the ~16 MiB VMEM budget,
and the batched (8x64x64)@(8x64x64) dot keeps the MXU pipeline full.

No causal mask (ViT windows are bidirectional); padded token rows are
masked by a static ``w2_valid`` length (windows like 9x9 = 81 pad to 88).
GQA is supported through the kv index_map (h -> h // group) although
ViTDet itself uses MHA (H == KV).

Length-bucketed padded sequences (core.partition.PlanLayout) add a
second, *runtime* mask next to the static ``w2_valid``: a per-window
valid flag (``win_flags``, (BW, 1) i32).  Window attention is
window-local, so a pad window never influences a valid one — the flag
only zeroes the pad windows' own outputs, keeping padded lanes
deterministic on every backend.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_WB = 8
NEG_INF = -2.0 ** 30


def _window_attend(q_ref, k_ref, v_ref, *, scale: float, w2_valid: int):
    q = q_ref[...].astype(jnp.float32)               # (WB, W2p, Dh)
    k = k_ref[...].astype(jnp.float32)
    v = v_ref[...].astype(jnp.float32)
    WB, W2p, _ = q.shape

    # batched dot: contract Dh, batch over the window axis
    s = jax.lax.dot_general(
        q, k, (((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32) * scale  # (WB, W2p, W2p)
    if w2_valid < W2p:
        k_pos = jax.lax.broadcasted_iota(jnp.int32, (WB, W2p, W2p), 2)
        s = jnp.where(k_pos < w2_valid, s, NEG_INF)

    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    p = p / jnp.sum(p, axis=-1, keepdims=True)
    return jax.lax.dot_general(
        p, v, (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)          # (WB, W2p, Dh)


def _window_kernel(q_ref, k_ref, v_ref, o_ref, *, scale: float,
                   w2_valid: int):
    o = _window_attend(q_ref, k_ref, v_ref, scale=scale, w2_valid=w2_valid)
    o_ref[...] = o.astype(o_ref.dtype)


def _window_kernel_flagged(q_ref, k_ref, v_ref, f_ref, o_ref, *,
                           scale: float, w2_valid: int):
    """Variant with a per-window runtime valid flag: pad windows (flag 0)
    emit zeros instead of attention over their replicated content."""
    o = _window_attend(q_ref, k_ref, v_ref, scale=scale, w2_valid=w2_valid)
    flags = f_ref[...]                               # (WB, 1) int32
    o = jnp.where(flags[:, :, None] > 0, o, 0.0)
    o_ref[...] = o.astype(o_ref.dtype)


def window_attention_kernel(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                            *, scale: float, w2_valid: int,
                            wb: int = DEFAULT_WB,
                            interpret: bool,
                            win_flags: jnp.ndarray = None) -> jnp.ndarray:
    """q: (BW, H, W2p, Dh); k/v: (BW, KV, W2p, Dh).  BW = batch*windows,
    BW % wb == 0, W2p % 8 == 0 (ops.py pads).  Returns q-shaped output.

    ``win_flags``: optional (BW, 1) i32 per-window valid flag (1 = real
    window, 0 = length-bucket pad — output zeroed)."""
    BW, H, W2p, Dh = q.shape
    KV = k.shape[1]
    group = H // KV
    in_specs = [
        pl.BlockSpec((wb, None, W2p, Dh), lambda i, h: (i, h, 0, 0)),
        pl.BlockSpec((wb, None, W2p, Dh),
                     lambda i, h: (i, h // group, 0, 0)),
        pl.BlockSpec((wb, None, W2p, Dh),
                     lambda i, h: (i, h // group, 0, 0)),
    ]
    args = (q, k, v)
    if win_flags is None:
        kernel = functools.partial(_window_kernel, scale=scale,
                                   w2_valid=w2_valid)
    else:
        kernel = functools.partial(_window_kernel_flagged, scale=scale,
                                   w2_valid=w2_valid)
        in_specs.append(pl.BlockSpec((wb, 1), lambda i, h: (i, 0)))
        args = (q, k, v, win_flags.astype(jnp.int32))
    return pl.pallas_call(
        kernel,
        grid=(BW // wb, H),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((wb, None, W2p, Dh),
                               lambda i, h: (i, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((BW, H, W2p, Dh), q.dtype),
        interpret=interpret,
    )(*args)
