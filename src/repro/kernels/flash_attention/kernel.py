"""Flash attention Pallas TPU kernel (online-softmax tiling, GQA-aware).

Tiling: grid = (batch, q_heads, n_q_blocks, n_kv_blocks); the LAST grid
axis iterates sequentially on TPU, so the kv axis is the accumulation
loop.  Per program the VMEM working set is

    q    (BQ, Dh)      one query block of one head
    k,v  (BK, Dh)      one kv block of the matching kv head (GQA: the
                       index_map folds h -> h // group into the kv head
                       axis, so grouped queries re-read the same kv block
                       from HBM — on TPU this is served by VMEM locality
                       across consecutive grid steps)
    acc  (BQ, Dh) f32  output accumulator   (scratch, persists over kv)
    m, l (BQ, 128) f32 running max / sum    (scratch)

Block shapes come from the call's shape (``default_blocks``).  A
non-causal call takes, for T and for S alike, the largest multiple of 128
up to ``BLOCK_MAX`` = 1024, and no longer than the sequence, that pads it
no further than 128-blocks do (ops.py still cuts a block to a sequence
shorter than 128).  At 4096 tokens that is 1024 x 1024, a grid of (B, H,
4, 4): K/V are read from HBM once per 1024 query rows instead of once per
128, and the accumulator is rescaled once per 1024 keys.  On a v5e one
call at f32[1,16,4096,64] takes 1.79 ms at 1024 x 1024 against 8.91 ms at
128 x 128 (the kernel alone 0.97 against 8.00 ms), and no pair is faster
at B = 1, 2 or 4 (``benchmarks/flash_blocks.py``; the sweep is in
PERF.md).  The (BQ, BK) f32 scores and probabilities, 4 MiB each, and the
double-buffered q/k/v blocks fit the default scoped VMEM at every B (1024
x 2048 does not, at B = 4).  Causal calls (LM prefill) keep 128 x 128:
masking on the diagonal is per element, and a larger block does more of
that masked work.  Every block is MXU-aligned: the two matmuls are (BQ x
Dh) @ (Dh x BK) and (BQ x BK) @ (BK x Dh), and Dh in {64, 128} is a
multiple of the 128x128 MXU tile or exactly half of it, which Mosaic
handles natively.

Causal masking: programs whose kv block lies entirely above the causal
diagonal still run (Pallas TPU grids are dense) but skip the matmuls via
``pl.when`` — only the (rare) diagonal blocks pay for the iota mask.

Per-row lengths (``kv_len``, the padded ViT's global blocks before the
restoration point): the lengths arrive as a scalar-prefetch operand, and
row b keeps its first kv_len[b] keys and query rows; the query rows past
it are padding and come out zero.  A block wholly past the length does
no work, and the index maps send it the row's last live block, which the
pipeline already holds, so no pad block is read from HBM; only the block
the length ends in pays for the iota mask.  Calls without ``kv_len``
build the kernel as before, with no scalar prefetch.

fp32 softmax throughout; inputs may be bf16/f32 (cast on load).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK_MAX = 1024
CAUSAL_BLOCK = 128
NEG_INF = -2.0 ** 30


def _fit(n: int, cap: int) -> int:
    """Largest multiple of 128, at most ``cap`` and at most ``n`` (or 128),
    that pads ``n`` to the same length as 128 does: 128 times the largest
    divisor of n's count of 128-blocks within those bounds."""
    m = -(-n // 128)
    top = max(1, min(cap, n) // 128)
    return 128 * max(d for d in range(1, top + 1) if m % d == 0)


def default_blocks(T: int, S: int, causal: bool) -> tuple:
    """(bq, bk) for a call with T queries and S keys (before ops.py clamps
    a block to a short sequence)."""
    if causal:
        return CAUSAL_BLOCK, CAUSAL_BLOCK
    return _fit(T, BLOCK_MAX), _fit(S, BLOCK_MAX)


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  scale: float, causal: bool, bq: int, bk: int,
                  n_kv_blocks: int, kv_valid: int, kv_len=None):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # causal: kv block strictly above the q block's last row -> all masked
    q_start = qi * bq
    k_start = ki * bk

    def _body(limit=None):
        """One kv block; keys at or past ``limit`` (traced) are masked."""
        q = q_ref[...].astype(jnp.float32)            # (BQ, Dh)
        k = k_ref[...].astype(jnp.float32)            # (BK, Dh)
        v = v_ref[...].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal or kv_valid % bk:
            q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            mask = (q_pos >= k_pos) if causal else (k_pos < kv_valid)
            if causal and kv_valid % bk:
                mask = mask & (k_pos < kv_valid)
            s = jnp.where(mask, s, NEG_INF)
        if limit is not None:
            k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(k_pos < limit, s, NEG_INF)

        m_prev = m_ref[:, 0]                          # (BQ,)
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1))
        alpha = jnp.exp(m_prev - m_cur)               # rescale of old acc
        p = jnp.exp(s - m_cur[:, None])               # (BQ, BK)
        l_ref[:, 0] = l_ref[:, 0] * alpha + jnp.sum(p, axis=1)
        m_ref[:, 0] = m_cur
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if kv_len is not None:   # skip blocks past the row's length
        live = (q_start < kv_len) & (k_start < kv_len)
        pl.when(live & (k_start + bk <= kv_len))(_body)
        pl.when(live & (k_start + bk > kv_len))(lambda: _body(kv_len))
    elif causal:   # skip kv blocks entirely above the causal diagonal
        pl.when(k_start <= q_start + bq - 1)(_body)
    else:
        _body()

    @pl.when(ki == n_kv_blocks - 1)
    def _finalize():
        l = l_ref[:, 0]
        # fully-masked rows (causal padding, skipped blocks) have l == 0
        # -> emit zeros
        inv = jnp.where(l > 0.0, 1.0 / jnp.maximum(l, 1e-30), 0.0)
        out = acc_ref[...] * inv[:, None]
        if kv_len is not None:   # query rows past the length: zeros
            rows = q_start + jax.lax.broadcasted_iota(jnp.int32, out.shape, 0)
            out = jnp.where(rows < kv_len, out, 0.0)
        o_ref[...] = out.astype(o_ref.dtype)


def flash_attention_kernel(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                           kv_len: Optional[jnp.ndarray] = None, *,
                           causal: bool, scale: float, bq: int, bk: int,
                           kv_valid: int = 0,
                           interpret: bool) -> jnp.ndarray:
    """q: (B, H, T, Dh); k/v: (B, KV, S, Dh); H = KV * G.  T % bq == 0,
    S % bk == 0 (ops.py pads).  ``kv_valid``: number of real (unpadded)
    keys; 0 means all S.  ``kv_len``: optional (B,) int32 per-row
    lengths of a non-causal self-attention (T == S, each at most the
    unpadded length): row b attends to its first kv_len[b] keys, and its
    query rows from kv_len[b] on come out zero.  Returns (B, H, T, Dh) in
    q.dtype."""
    B, H, T, Dh = q.shape
    KV, S = k.shape[1], k.shape[2]
    group = H // KV
    n_q = T // bq
    n_k = S // bk
    kv_valid = S if kv_len is not None else (kv_valid or S)

    grid = (B, H, n_q, n_k)
    if kv_len is None:
        def q_map(b, h, q_, k_):
            return (b, h, q_, 0)

        def kv_map(b, h, q_, k_):
            return (b, h // group, k_, 0)

        o_map = q_map
    else:
        def last(n, blk):    # the last block holding a row < n (0 if none)
            return jnp.maximum(n - 1, 0) // blk

        def q_map(b, h, q_, k_, lens):
            return (b, h, jnp.minimum(q_, last(lens[b], bq)), 0)

        def kv_map(b, h, q_, k_, lens):
            kl = last(lens[b], bk)
            live_q = q_ <= last(lens[b], bq)
            return (b, h // group,
                    jnp.where(live_q, jnp.minimum(k_, kl), kl), 0)

        def o_map(b, h, q_, k_, lens):
            return (b, h, q_, 0)

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, bq=bq, bk=bk,
        n_kv_blocks=n_k, kv_valid=kv_valid)
    specs = dict(
        in_specs=[
            pl.BlockSpec((None, None, bq, Dh), q_map),
            pl.BlockSpec((None, None, bk, Dh), kv_map),
            pl.BlockSpec((None, None, bk, Dh), kv_map),
        ],
        out_specs=pl.BlockSpec((None, None, bq, Dh), o_map),
        scratch_shapes=[
            pltpu.VMEM((bq, Dh), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
        ],
    )
    out_shape = jax.ShapeDtypeStruct((B, H, T, Dh), q.dtype)
    if kv_len is None:
        return pl.pallas_call(kernel, grid=grid, out_shape=out_shape,
                              interpret=interpret, **specs)(q, k, v)

    def masked(lens_ref, *refs):
        kernel(*refs, kv_len=lens_ref[pl.program_id(0)])

    return pl.pallas_call(
        masked,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=grid, **specs),
        out_shape=out_shape,
        interpret=interpret,
    )(kv_len, q, k, v)
