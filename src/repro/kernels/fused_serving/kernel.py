"""Fused serving prologue/epilogue Pallas TPU kernels.

The padded serving forward used to be a chain of separate dispatches —
window-bank gather (pack), positional-embedding add, pad-window zeroing,
then after the blocks a sentinel-row scatter + low-window upsample +
reuse-tile splice (restore) — each materialising the full packed
sequence in HBM.  These two kernels collapse each seam into one pass:

  pack_pos_kernel       out[b, i] = bank[b, win_src[b, i]]
                                    + pos_bank[win_src[b, i]]   if i < nw[b]
                        else 0
                        One window per program; ``win_src``/``nw`` arrive
                        via scalar prefetch so the bank BlockSpec gathers
                        the source window directly from HBM — the packed
                        sequence is written exactly once.

  restore_gather_kernel out[b, o] = perm[out_map[b, o]]
                                    @ src[b, out_src[b, o]]
                        The destination-major inverse of the restoration
                        scatter: every output window (full-res grid slot)
                        gathers its source window — a packed FULL window
                        (perm 0 = identity), the region's LOW window
                        through one of d^2 upsample permutations, or a
                        spliced reuse tile.  ``perm`` is a small
                        (d^2+1, w^2, w^2) one-hot table; the gather is an
                        MXU matmul at HIGHEST precision (one-hot f32 rows
                        select exactly one finite activation row, so the
                        product is bit-identical to the scatter
                        formulation).

Both kernels are pure data movement + one add/matmul, so their Pallas
and jnp reference paths agree bitwise (tests/test_fused_serving.py).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _pack_pos_body(src_ref, nw_ref, bank_ref, pos_ref, o_ref):
    b = pl.program_id(0)
    i = pl.program_id(1)
    x = bank_ref[...] + pos_ref[...]
    o_ref[...] = jnp.where(i < nw_ref[b], x, jnp.zeros_like(x))


def pack_pos_kernel(bank: jnp.ndarray, pos_bank: jnp.ndarray,
                    win_src: jnp.ndarray, nw: jnp.ndarray, *,
                    interpret: bool) -> jnp.ndarray:
    """bank: (B, nbank, w2, C); pos_bank: (nbank, w2, C);
    win_src: (B, nw_pad) i32; nw: (B,) i32.  Returns (B, nw_pad, w2, C)
    packed windows with positions added and pad windows zeroed."""
    B, nbank, w2, C = bank.shape
    nw_pad = win_src.shape[1]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, nw_pad),
        in_specs=[
            pl.BlockSpec((None, None, w2, C),
                         lambda b, i, src, nw_: (b, src[b, i], 0, 0)),
            pl.BlockSpec((None, w2, C),
                         lambda b, i, src, nw_: (src[b, i], 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, None, w2, C),
                               lambda b, i, src, nw_: (b, i, 0, 0)),
    )
    return pl.pallas_call(
        _pack_pos_body,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, nw_pad, w2, C), bank.dtype),
        interpret=interpret,
    )(win_src, nw, bank, pos_bank)


def _restore_body(src_idx_ref, map_idx_ref, src_ref, perm_ref, o_ref):
    blk = src_ref[...].astype(jnp.float32)            # (w2, D)
    # HIGHEST: at Mosaic's default precision an f32 matmul rounds its
    # operands to bf16, which would round every restored activation
    o_ref[...] = jax.lax.dot_general(
        perm_ref[...], blk, (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32).astype(o_ref.dtype)


def restore_gather_kernel(src: jnp.ndarray, perm: jnp.ndarray,
                          out_src: jnp.ndarray, out_map: jnp.ndarray, *,
                          interpret: bool) -> jnp.ndarray:
    """src: (B, nsrc, w2, D) source bank [packed windows | reuse tiles];
    perm: (d^2+1, w2, w2) f32 one-hot token permutations; out_src /
    out_map: (B, nout) i32.  Returns (B, nout, w2, D) restored windows
    in full-res grid slot order."""
    B, nsrc, w2, D = src.shape
    nout = out_src.shape[1]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, nout),
        in_specs=[
            pl.BlockSpec((None, None, w2, D),
                         lambda b, o, si, mi: (b, si[b, o], 0, 0)),
            pl.BlockSpec((None, w2, w2),
                         lambda b, o, si, mi: (mi[b, o], 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, None, w2, D),
                               lambda b, o, si, mi: (b, o, 0, 0)),
    )
    return pl.pallas_call(
        _restore_body,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, nout, w2, D), src.dtype),
        interpret=interpret,
    )(out_src, out_map, src, perm)
