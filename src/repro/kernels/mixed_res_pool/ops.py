"""jit'd entry points for the mixed-res pooling kernels.

``avg_pool_2d`` hands the kernel the grid as (B*C, H, W) planes — a free
relayout for a few-channel image, which XLA already keeps channel-planar
on the TPU — and sizes its plane and row blocks from a VMEM budget, so a
1024^2 RGB frame pools without padding 3 channels to 128 lanes.
``nn_upsample_2d`` pads channels to the lane tile and picks a row block
that divides the grid.  Off-TPU both run in interpret mode.

Both ops carry closed-form ``jax.custom_vjp``s (average pool's adjoint
is nearest-upsample / d^2 and vice versa), so the Pallas pooling lane
is differentiable without an XLA fallback.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.mixed_res_pool import kernel as K

# VMEM the pool's blocks may take: v5e gives a kernel 16 MiB of scoped
# VMEM by default, and half of it leaves room for the compiler's own.
POOL_VMEM_BUDGET = 8 * 1024 * 1024


def _plan(n: int, want: int) -> int:
    rb = min(want, n)
    while n % rb:
        rb -= 1
    return rb


def _pad_c(x: jnp.ndarray, bc: int):
    C = x.shape[-1]
    pad = (-C) % bc
    if pad:
        x = jnp.pad(x, ((0, 0),) * (x.ndim - 1) + ((0, pad),))
    return x, C


def _tiled_divisors(n: int, tile: int):
    """Divisors of ``n`` that are a multiple of ``tile`` or ``n`` itself,
    largest first — the block sizes Mosaic accepts along a tiled axis."""
    return [b for b in range(n, 0, -1)
            if n % b == 0 and (b % tile == 0 or b == n)]


def _lanes(n: int) -> int:
    return -(-n // 128) * 128


def pool_blocks(N: int, Ho: int, L: int, Wo: int,
                itemsize: int) -> Tuple[int, int]:
    """(nb, rb) for the (nb, rb, L) -> (nb, rb, Wo) pool block: the
    largest row block, then the most planes, whose double-buffered input
    and output plus f32 temporaries (lanes padded to the 128-lane tile)
    fit ``POOL_VMEM_BUDGET``; the smallest legal block when nothing
    fits.  Planes only stack (nb > 1) when rb is a whole sublane tile,
    so the kernel's (nb*rb, L) matmul view is free."""
    sub = 32 // itemsize
    per_row = (2 * (_lanes(L) + _lanes(Wo)) * itemsize
               + (2 * _lanes(L) + _lanes(Wo)) * 4)
    cap = max(POOL_VMEM_BUDGET // per_row, 1)
    rbs = _tiled_divisors(Ho, sub)
    rb = next((r for r in rbs if r <= cap), rbs[-1])
    if rb % sub:
        return 1, rb
    nb = next((n for n in range(N, 0, -1) if N % n == 0 and n * rb <= cap),
              1)
    return nb, rb


def pool_matrix(W: int, d: int) -> Tuple[np.ndarray, int]:
    """The kernel's f32 pooling matrix and tap count for width ``W``.

    When W holds whole 128*d-lane chunks, the d row taps are summed as
    lane-aligned slices and the matrix pools one chunk's columns:
    (128*d, 128), taps = d.  Otherwise one matrix pools the whole d*W
    row pair, row and column taps at once: (d*W, W/d), taps = 1.  Each
    entry is 1/d^2 where an input lane feeds an output lane."""
    if W % (128 * d) == 0:
        cw = 128 * d
        m = np.zeros((cw, cw // d), np.float32)
        m[np.arange(cw), np.arange(cw) // d] = 1.0 / (d * d)
        return m, d
    lane = np.arange(d * W)
    m = np.zeros((d * W, W // d), np.float32)
    m[lane, (lane % W) // d] = 1.0 / (d * d)
    return m, 1


@functools.partial(jax.jit, static_argnames=("d", "interpret"))
def _avg_pool_fwd(x, *, d, interpret):
    B, H, W, C = x.shape
    Ho, Wo = H // d, W // d
    planes = x.transpose(0, 3, 1, 2).reshape(B * C, Ho, d * W)
    pool, taps = pool_matrix(W, d)
    nb, rb = pool_blocks(B * C, Ho, d * W, Wo, x.dtype.itemsize)
    out = K.avg_pool_kernel(planes, jnp.asarray(pool), taps=taps, nb=nb,
                            rb=rb, interpret=interpret)
    return out.reshape(B, C, Ho, Wo).transpose(0, 2, 3, 1)


@functools.partial(jax.jit, static_argnames=("d", "rb", "bc", "interpret"))
def _nn_upsample_fwd(x, *, d, rb, bc, interpret):
    B, H, W, C0 = x.shape
    bc_ = min(bc, ((C0 + 7) // 8) * 8)
    x, C0 = _pad_c(x, bc_)
    rb_ = _plan(H, rb)
    out = K.nn_upsample_kernel(x, d, rb=rb_, bc=bc_, interpret=interpret)
    return out[..., :C0]


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def _avg_pool(x, d, interpret):
    return _avg_pool_fwd(x, d=d, interpret=interpret)


def _avg_pool_vfwd(x, d, interpret):
    return _avg_pool(x, d, interpret), None


def _avg_pool_vbwd(d, interpret, _, g):
    # adjoint of mean pooling: broadcast each pooled cotangent back over
    # its d x d block, scaled by 1/d^2
    dx = jnp.repeat(jnp.repeat(g, d, axis=1), d, axis=2) / (d * d)
    return (dx.astype(g.dtype),)


_avg_pool.defvjp(_avg_pool_vfwd, _avg_pool_vbwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4))
def _nn_upsample(x, d, rb, bc, interpret):
    return _nn_upsample_fwd(x, d=d, rb=rb, bc=bc, interpret=interpret)


def _nn_upsample_vfwd(x, d, rb, bc, interpret):
    return _nn_upsample(x, d, rb, bc, interpret), None


def _nn_upsample_vbwd(d, rb, bc, interpret, _, g):
    # adjoint of nearest-neighbour replication: sum each d x d block
    B, H, W, C = g.shape
    dx = g.reshape(B, H // d, d, W // d, d, C).sum(axis=(2, 4))
    return (dx.astype(g.dtype),)


_nn_upsample.defvjp(_nn_upsample_vfwd, _nn_upsample_vbwd)


def avg_pool_2d(x: jnp.ndarray, d: int, *,
                interpret: Optional[bool] = None) -> jnp.ndarray:
    """Drop-in for core.mixed_res.downsample_grid.  x: (B, H, W, C)
    with H and W divisible by d."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if d == 1:
        return x
    return _avg_pool(x, int(d), bool(interpret))


def nn_upsample_2d(x: jnp.ndarray, d: int, *, rb: int = K.DEFAULT_RB,
                   bc: int = K.DEFAULT_BC,
                   interpret: Optional[bool] = None) -> jnp.ndarray:
    """Nearest-neighbour upsample (restoration §III-B).  x: (B, H, W, C)."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if d == 1:
        return x
    return _nn_upsample(x, int(d), int(rb), int(bc), bool(interpret))
