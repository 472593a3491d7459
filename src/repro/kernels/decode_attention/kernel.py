"""One-token GQA decode attention Pallas TPU kernel (flash-decode style).

Serving decode reads a (B, S, KV, Dh) cache with one fresh query token;
the op is memory-bound (arithmetic intensity ~ 1 FLOP/byte), so the
kernel's job is to stream the cache through VMEM exactly once at full
HBM bandwidth while the VPU does the online softmax.

Tiling: grid = (B, KV, n_s_blocks); the cache axis iterates sequentially
and accumulates in VMEM scratch.  The G = H/KV grouped queries of one kv
head form the row axis of the matmuls (padded to sublane granularity),
so GQA grouping is what provides MXU rows — the bigger the group, the
better the utilisation (the roofline §Perf notes rely on this).

    q     (Gp, Dh)        one kv head's query group
    k,v   (BS, Dh)        one cache block
    acc   (Gp, Dh) f32    output accumulator (scratch)
    m, l  (Gp, 128) f32   running max / sum  (scratch)

Per-sequence valid lengths arrive via scalar prefetch (kv_len, int32
(B,)): blocks entirely beyond kv_len[b] are skipped with ``pl.when`` —
for a half-full ring buffer this halves the HBM traffic, which is the
whole cost of decode.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BS = 512
NEG_INF = -2.0 ** 30


def _decode_kernel(kv_len_ref, q_ref, k_ref, v_ref, o_ref,
                   acc_ref, m_ref, l_ref, *, scale: float, bs: int,
                   n_s_blocks: int):
    b = pl.program_id(0)
    si = pl.program_id(2)
    kv_len = kv_len_ref[b]

    @pl.when(si == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    s_start = si * bs

    @pl.when(s_start < kv_len)       # skip blocks beyond the valid cache
    def _body():
        q = q_ref[...].astype(jnp.float32)           # (Gp, Dh)
        k = k_ref[...].astype(jnp.float32)           # (BS, Dh)
        v = v_ref[...].astype(jnp.float32)
        Gp = q.shape[0]

        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        k_pos = s_start + jax.lax.broadcasted_iota(jnp.int32, (Gp, bs), 1)
        s = jnp.where(k_pos < kv_len, s, NEG_INF)

        m_prev = m_ref[:, 0]
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur[:, None])
        l_ref[:, 0] = l_ref[:, 0] * alpha + jnp.sum(p, axis=1)
        m_ref[:, 0] = m_cur
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(si == n_s_blocks - 1)
    def _finalize():
        l = l_ref[:, 0]
        inv = jnp.where(l > 0.0, 1.0 / jnp.maximum(l, 1e-30), 0.0)
        o_ref[...] = (acc_ref[...] * inv[:, None]).astype(o_ref.dtype)


def decode_attention_kernel(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                            kv_len: jnp.ndarray, *, scale: float,
                            bs: int = DEFAULT_BS,
                            interpret: bool) -> jnp.ndarray:
    """q: (B, KV, Gp, Dh); k/v: (B, KV, S, Dh); kv_len: (B,) int32.
    S % bs == 0 (ops.py pads).  Returns (B, KV, Gp, Dh) in q.dtype."""
    B, KV, Gp, Dh = q.shape
    S = k.shape[2]
    n_s = S // bs
    kernel = functools.partial(_decode_kernel, scale=scale, bs=bs,
                               n_s_blocks=n_s)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, KV, n_s),
        in_specs=[
            pl.BlockSpec((None, None, Gp, Dh),
                         lambda b, h, s, kv_len: (b, h, 0, 0)),
            pl.BlockSpec((None, None, bs, Dh),
                         lambda b, h, s, kv_len: (b, h, s, 0)),
            pl.BlockSpec((None, None, bs, Dh),
                         lambda b, h, s, kv_len: (b, h, s, 0)),
        ],
        out_specs=pl.BlockSpec((None, None, Gp, Dh),
                               lambda b, h, s, kv_len: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((Gp, Dh), jnp.float32),
            pltpu.VMEM((Gp, 128), jnp.float32),
            pltpu.VMEM((Gp, 128), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, Gp, Dh), q.dtype),
        interpret=interpret,
    )(kv_len, q, k, v)
