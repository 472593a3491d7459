"""int8 weight-quantized GEMM Pallas TPU kernel.

The quantized serving lane (repro.quant) stores every linear weight as
per-output-channel symmetric int8 (``q * scale`` recovers the float
weight) and quantizes activations per row on the fly, so the MXU runs a
native int8 x int8 -> int32 matmul and the float scales are applied once
in the epilogue:

    out[m, n] = (sum_k xq[m, k] * wq[k, n]) * sx[m] * sw[n]

Tiling: classic blocked GEMM with the K loop as the innermost grid
dimension and an int32 VMEM accumulator that lives across the K steps —
zeroed at k == 0, scaled/cast to the output dtype at k == nk-1.  int8
operands want (32, 128)-aligned tiles on the MXU; ops.py pads every
dimension up to the resolved block sizes, and zero-padding is exact
(padded K contributes 0 to the accumulator, padded M/N rows are sliced
off by the caller).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BM = 256
DEFAULT_BN = 256
DEFAULT_BK = 256


def _matmul_kernel(x_ref, w_ref, sx_ref, sw_ref, o_ref, acc_ref, *,
                   nk: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        x_ref[...], w_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)

    @pl.when(k == nk - 1)
    def _epilogue():
        acc = acc_ref[...].astype(jnp.float32)
        out = acc * sx_ref[...] * sw_ref[...]        # (bm,1) x (1,bn)
        o_ref[...] = out.astype(o_ref.dtype)


def int8_matmul_kernel(xq: jnp.ndarray, wq: jnp.ndarray,
                       sx: jnp.ndarray, sw: jnp.ndarray, *,
                       out_dtype=jnp.float32,
                       bm: int = DEFAULT_BM, bn: int = DEFAULT_BN,
                       bk: int = DEFAULT_BK,
                       interpret: bool) -> jnp.ndarray:
    """xq: (M, K) int8; wq: (K, N) int8; sx: (M, 1) f32 per-row
    activation scales; sw: (1, N) f32 per-output-channel weight scales.
    M % bm == K % bk == N % bn == 0 (ops.py pads).  Returns (M, N) in
    ``out_dtype``."""
    M, K = xq.shape
    N = wq.shape[1]
    nk = K // bk
    kernel = functools.partial(_matmul_kernel, nk=nk)
    return pl.pallas_call(
        kernel,
        grid=(M // bm, N // bn, nk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((bm, 1), lambda i, j, k: (i, 0)),
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        interpret=interpret,
    )(xq, wq, sx, sw)
