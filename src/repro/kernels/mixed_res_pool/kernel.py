"""Mixed-resolution pooling / restoration Pallas TPU kernels.

Two small data-movement kernels backing the paper's §III hot spots:

  avg_pool      d x d average pooling of a (B, H, W, C) patch/pixel grid —
                the device-side downsampling step of §III-A (pack_mixed /
                downsample_grid).
  nn_upsample   d x d nearest-neighbour broadcast — the restoration-point
                upsample of §III-B (restore_full).

Both are bandwidth-bound reshapes+reductions; the kernels exist to keep
the op on-chip when fused into the packing pipeline (HBM -> VMEM once).

avg_pool reads the grid *planar* — (B*C, H/d, d*W), W on lanes, the d
input rows of each output row side by side — which is how XLA already
lays out a few-channel image on the TPU, so an RGB frame neither pads 3
channels to 128 lanes nor needs a relayout.  The d row taps are lane
slices and the d column taps, with their compaction to W/d lanes, one
matmul against a constant 1/d^2 pooling matrix (HIGHEST precision):

    avg_pool grid = (N / NB, Ho / RB)
      in  block (NB, RB, d*W)   pool (CW, CO)   out block (NB, RB, W/d)
    upsample grid = (B, Hin / RB, C / BC)
      in  block (RB,   W,   BC)      out block (RB*d, W*d, BC)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_BC = 128
DEFAULT_RB = 8


def _avg_pool_kernel(x_ref, m_ref, o_ref, *, taps: int, stride: int):
    nb, rb, wo = o_ref.shape
    x = x_ref[...].astype(jnp.float32)                # (NB, RB, d*W)
    x = x.reshape(nb * rb, x.shape[-1])
    m = m_ref[...]                                    # (CW, CO)
    cw, co = m.shape
    for j in range(wo // co):
        rows = x[:, j * cw:(j + 1) * cw]
        for a in range(1, taps):
            lo = a * stride + j * cw
            rows = rows + x[:, lo:lo + cw]
        o = jnp.dot(rows, m, precision=jax.lax.Precision.HIGHEST,
                    preferred_element_type=jnp.float32)
        o_ref[:, :, j * co:(j + 1) * co] = o.reshape(nb, rb, co).astype(
            o_ref.dtype)


def _nn_upsample_kernel(x_ref, o_ref, *, d: int):
    x = x_ref[...]                                    # (RB, W, BC)
    RB, W, BC = x.shape
    x = jnp.broadcast_to(x[:, None, :, None, :], (RB, d, W, d, BC))
    o_ref[...] = x.reshape(RB * d, W * d, BC)


def avg_pool_kernel(x: jnp.ndarray, pool: jnp.ndarray, *, taps: int,
                    nb: int, rb: int, interpret: bool):
    """x: (N, Ho, d*W) row-paired planes -> (N, Ho, W/d) d x d means.

    ``pool`` is (CW, CO): with ``taps == d`` it pools the columns of one
    CW-lane chunk of a row, and the kernel first sums the d rows' chunks
    (lane slices W apart); with ``taps == 1`` it pools the whole d*W row
    pair at once.  ``nb`` divides N; ``rb`` divides Ho and is a multiple
    of the sublane tile or the whole axis (ops.py picks all three)."""
    N, Ho, L = x.shape
    cw, co = pool.shape
    Wo = L // taps // (cw // co)
    return pl.pallas_call(
        functools.partial(_avg_pool_kernel, taps=taps, stride=L // taps),
        grid=(N // nb, Ho // rb),
        in_specs=[pl.BlockSpec((nb, rb, L), lambda n, i: (n, i, 0)),
                  pl.BlockSpec((cw, co), lambda n, i: (0, 0))],
        out_specs=pl.BlockSpec((nb, rb, Wo), lambda n, i: (n, i, 0)),
        out_shape=jax.ShapeDtypeStruct((N, Ho, Wo), x.dtype),
        interpret=interpret,
    )(x, pool)


def nn_upsample_kernel(x: jnp.ndarray, d: int, *, rb: int, bc: int,
                       interpret: bool):
    """x: (B, H, W, C) with H % rb == 0, C % bc == 0 -> (B, H*d, W*d, C)."""
    B, H, W, C = x.shape
    return pl.pallas_call(
        functools.partial(_nn_upsample_kernel, d=d),
        grid=(B, H // rb, C // bc),
        in_specs=[pl.BlockSpec((None, rb, W, bc),
                               lambda b, i, c: (b, i, 0, c))],
        out_specs=pl.BlockSpec((None, rb * d, W * d, bc),
                               lambda b, i, c: (b, i, 0, c)),
        out_shape=jax.ShapeDtypeStruct((B, H * d, W * d, C), x.dtype),
        interpret=interpret,
    )(x)
