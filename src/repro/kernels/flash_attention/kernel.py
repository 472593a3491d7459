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

Block shapes default to BQ = BK = 128 — MXU-aligned (the two matmuls are
(BQ x Dh) @ (Dh x BK) and (BQ x BK) @ (BK x Dh); with Dh in {64, 128}
every contraction dim is a multiple of the 128x128 MXU tile or exactly
half of it, which Mosaic handles natively).

Causal masking: programs whose kv block lies entirely above the causal
diagonal still run (Pallas TPU grids are dense) but skip the matmuls via
``pl.when`` — only the (rare) diagonal blocks pay for the iota mask.

fp32 softmax throughout; inputs may be bf16/f32 (cast on load).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BQ = 128
DEFAULT_BK = 128
NEG_INF = -2.0 ** 30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  scale: float, causal: bool, bq: int, bk: int,
                  n_kv_blocks: int, kv_valid: int):
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

    def _body():
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

        m_prev = m_ref[:, 0]                          # (BQ,)
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1))
        alpha = jnp.exp(m_prev - m_cur)               # rescale of old acc
        p = jnp.exp(s - m_cur[:, None])               # (BQ, BK)
        l_ref[:, 0] = l_ref[:, 0] * alpha + jnp.sum(p, axis=1)
        m_ref[:, 0] = m_cur
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:   # skip kv blocks entirely above the causal diagonal
        pl.when(k_start <= q_start + bq - 1)(_body)
    else:
        _body()

    @pl.when(ki == n_kv_blocks - 1)
    def _finalize():
        l = l_ref[:, 0]
        # fully-masked rows (causal padding) have l == 0 -> emit zeros
        inv = jnp.where(l > 0.0, 1.0 / jnp.maximum(l, 1e-30), 0.0)
        o_ref[...] = (acc_ref[...] * inv[:, None]).astype(o_ref.dtype)


def flash_attention_kernel(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                           causal: bool, scale: float,
                           bq: int = DEFAULT_BQ, bk: int = DEFAULT_BK,
                           kv_valid: int = 0,
                           interpret: bool) -> jnp.ndarray:
    """q: (B, H, T, Dh); k/v: (B, KV, S, Dh); H = KV * G.  T % bq == 0,
    S % bk == 0 (ops.py pads).  ``kv_valid``: number of real (unpadded)
    keys; 0 means all S.  Returns (B, H, T, Dh) in q.dtype."""
    B, H, T, Dh = q.shape
    KV, S = k.shape[1], k.shape[2]
    group = H // KV
    n_q = T // bq
    n_k = S // bk
    kv_valid = kv_valid or S

    grid = (B, H, n_q, n_k)
    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, bq=bq, bk=bk,
        n_kv_blocks=n_k, kv_valid=kv_valid)

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, None, bq, Dh),
                         lambda b, h, q_, k_: (b, h, q_, 0)),
            pl.BlockSpec((None, None, bk, Dh),
                         lambda b, h, q_, k_: (b, h // group, k_, 0)),
            pl.BlockSpec((None, None, bk, Dh),
                         lambda b, h, q_, k_: (b, h // group, k_, 0)),
        ],
        out_specs=pl.BlockSpec((None, None, bq, Dh),
                               lambda b, h, q_, k_: (b, h, q_, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, T, Dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, Dh), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
