"""Attention variants: GQA (full/causal/windowed), KV-cache decode, MLA.

Layouts:  activations (B, T, D);  q/k/v (B, T, H, Dh);  caches are
preallocated to the max sequence length and updated in place with
``dynamic_update_slice`` so serving graphs stay static-shaped.
"""
from __future__ import annotations

import contextlib
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import dispatch
from repro.models.config import ModelConfig
from repro.models.layers import apply_rope, dense_init, rms_norm
from repro.quant import qtensor as qt

NEG_INF = -2.0 ** 30   # large-finite: avoids NaN rows for fully-masked queries


# ---------------------------------------------------------------------------
# head-importance tap (quant.prune calibration)
#
# When armed, every EAGER attention_forward appends the per-head mean
# |output| (pre-w_o) to the store — the ViT backbone makes exactly
# n_layers attention calls per forward, in layer order, so the store
# reshapes to (frames, layers, heads).  Traced calls never record (the
# tap reads concrete values); the serving/training hot paths see one
# ``is None`` check.

_HEAD_TAP: Optional[List[np.ndarray]] = None


@contextlib.contextmanager
def head_tap(store: List[np.ndarray]):
    """Arm the per-head output-magnitude tap for eager calibration."""
    global _HEAD_TAP
    prev = _HEAD_TAP
    _HEAD_TAP = store
    try:
        yield store
    finally:
        _HEAD_TAP = prev


# ---------------------------------------------------------------------------
# scaled dot-product attention (grouped-query aware, fp32 softmax)


Q_CHUNK = 1024   # flash-style q-block size for long sequences


def sdpa(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
         causal: bool = False,
         q_offset: int | jnp.ndarray = 0,
         kv_len: Optional[jnp.ndarray] = None,
         scale: Optional[float] = None,
         backend: Optional[str] = None) -> jnp.ndarray:
    """q: (B,T,H,Dh)  k/v: (B,S,KV,Dh) with H = KV * G.  Returns (B,T,H,Dh).

    ``q_offset``: absolute position of q[0] (decode: pos; prefill: 0).
    ``kv_len``: optional per-batch valid length (B,): the cache length
    for decode, the real tokens of a padded sequence otherwise.
    ``backend``: kernel backend (kernels.dispatch).  The Pallas lane
    routes three shapes: the plain full-sequence case and the padded
    non-causal self-attention with a ``kv_len`` (the padded ViT's
    pre-restoration global blocks) to the flash kernel, and the
    one-token ``kv_len`` cache read (T == 1) to the decode kernel
    (kernels/decode_attention).  The flash kernel zeroes the query rows
    from a row's ``kv_len`` on, where XLA computes them: they are pad
    tokens, which the ViT never reads.  Everything else — nonzero ``q_offset``, explicit
    ``scale``, ``kv_len`` with causal or cross-length attention — stays
    on the XLA path.

    Long sequences (T > 2*Q_CHUNK) are processed as a lax.scan over query
    blocks so the live logits buffer is (B, C, H, S) instead of the full
    (B, T, H, S) — the XLA analogue of flash attention's tiling (the
    Pallas kernel in kernels/flash_attention does the same on-chip).
    """
    aligned = isinstance(q_offset, int) and q_offset == 0 and scale is None
    padded = (kv_len is not None and not causal
              and 1 < q.shape[1] == k.shape[1])
    if (aligned and (kv_len is None or padded)
            and dispatch.use_pallas(backend)):
        return dispatch.flash_attention(q, k, v, causal=causal,
                                        kv_len=kv_len)
    decode = (kv_len is not None and q.shape[1] == 1 and not causal
              and aligned)
    if decode and dispatch.use_pallas(backend):
        return dispatch.decode_attention(q, k, v, kv_len)
    T = q.shape[1]
    if T > 2 * Q_CHUNK:
        return _sdpa_blocked(q, k, v, causal=causal, q_offset=q_offset,
                             kv_len=kv_len, scale=scale)
    return _sdpa_dense(q, k, v, causal=causal, q_offset=q_offset,
                       kv_len=kv_len, scale=scale)


def _sdpa_blocked(q, k, v, *, causal, q_offset, kv_len, scale):
    B, T0, H, Dh = q.shape
    if T0 % Q_CHUNK:                      # pad q rows; sliced off below
        q = jnp.pad(q, ((0, 0), (0, Q_CHUNK - T0 % Q_CHUNK),
                        (0, 0), (0, 0)))
    T = q.shape[1]
    nb = T // Q_CHUNK
    qb = jnp.moveaxis(q.reshape(B, nb, Q_CHUNK, H, Dh), 1, 0)
    offs = jnp.arange(nb) * Q_CHUNK + q_offset

    def body(_, inp):
        qblk, off = inp
        out = _sdpa_dense(qblk, k, v, causal=causal, q_offset=off,
                          kv_len=kv_len, scale=scale)
        return None, out

    from repro.models.layers import scan as _scan
    _, outs = _scan(body, None, (qb, offs))
    return jnp.moveaxis(outs, 0, 1).reshape(B, T, H, Dh)[:, :T0]


def _sdpa_dense(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                causal: bool = False,
                q_offset: int | jnp.ndarray = 0,
                kv_len: Optional[jnp.ndarray] = None,
                scale: Optional[float] = None) -> jnp.ndarray:
    B, T, H, Dh = q.shape
    S, KV = k.shape[1], k.shape[2]
    G = H // KV
    scale = scale if scale is not None else Dh ** -0.5
    qg = q.reshape(B, T, KV, G, Dh)
    logits = jnp.einsum("btkgd,bskd->bkgts", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    if causal:
        q_pos = jnp.arange(T) + q_offset
        k_pos = jnp.arange(S)
        mask = q_pos[:, None] >= k_pos[None, :]
        logits = jnp.where(mask[None, None, None], logits, NEG_INF)
    if kv_len is not None:
        valid = jnp.arange(S)[None, :] < kv_len[:, None]          # (B,S)
        logits = jnp.where(valid[:, None, None, None], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgts,bskd->btkgd", probs, v.astype(jnp.float32))
    return out.reshape(B, T, H, Dh).astype(q.dtype)


def window_sdpa(q, k, v, window: int, *,
                win_valid: Optional[jnp.ndarray] = None,
                backend: Optional[str] = None) -> jnp.ndarray:
    """Non-overlapping local window attention over a 1-D sequence.

    q/k/v: (B, T, H, Dh) with T % window == 0.  Each window attends only
    to itself (ViTDet-style window attention, 1-D layout).  ``backend``
    routes to the Pallas window-attention kernel (kernels.dispatch).

    ``win_valid``: optional (B,) i32 count of VALID windows per sample
    (length-bucketed padded sequences, core.partition.PlanLayout): pad
    windows beyond the count have their outputs zeroed, so padded lanes
    carry deterministic content on both backends.  Window attention is
    window-local, so valid windows are unaffected either way.
    """
    if dispatch.use_pallas(backend):
        return dispatch.window_attention(q, k, v, window,
                                         win_valid=win_valid)
    B, T, H, Dh = q.shape
    W = T // window
    qw = q.reshape(B, W, window, H, Dh).reshape(B * W, window, H, Dh)
    kw = k.reshape(B, W, window, k.shape[2], Dh).reshape(B * W, window, -1, Dh)
    vw = v.reshape(B, W, window, v.shape[2], Dh).reshape(B * W, window, -1, Dh)
    out = sdpa(qw, kw, vw, causal=False)
    out = out.reshape(B, W, window, H, Dh)
    if win_valid is not None:
        keep = jnp.arange(W)[None, :] < win_valid[:, None]       # (B, W)
        out = jnp.where(keep[:, :, None, None, None], out, 0)
    return out.reshape(B, T, H, Dh)


# ---------------------------------------------------------------------------
# standard GQA attention layer


def init_attention(cfg: ModelConfig, key, dtype):
    ks = jax.random.split(key, 6)
    p = {
        "w_q": dense_init(ks[0], (cfg.d_model, cfg.q_dim), dtype),
        "w_k": dense_init(ks[1], (cfg.d_model, cfg.kv_dim), dtype),
        "w_v": dense_init(ks[2], (cfg.d_model, cfg.kv_dim), dtype),
        "w_o": dense_init(ks[3], (cfg.q_dim, cfg.d_model), dtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((cfg.head_dim,), dtype)
        p["k_norm"] = jnp.ones((cfg.head_dim,), dtype)
    if cfg.attention_bias:
        p["b_q"] = jnp.zeros((cfg.q_dim,), dtype)
        p["b_k"] = jnp.zeros((cfg.kv_dim,), dtype)
        p["b_v"] = jnp.zeros((cfg.kv_dim,), dtype)
        p["b_o"] = jnp.zeros((cfg.d_model,), dtype)
    return p


def _project_qkv(cfg: ModelConfig, p, x, positions, rope: bool = True):
    B, T, _ = x.shape
    if T == 1:
        # decode: the fused-weight concat below copies the whole QKV
        # weight per step, which dominates a single-token GEMV — keep
        # the three small GEMMs here.
        q = qt.matmul(x, p["w_q"])
        k = qt.matmul(x, p["w_k"])
        v = qt.matmul(x, p["w_v"])
        if cfg.attention_bias:
            q, k, v = q + p["b_q"], k + p["b_k"], v + p["b_v"]
    else:
        # fused QKV: one (D, q_dim + 2*kv_dim) GEMM instead of three —
        # each output column depends only on its own weight column, so
        # the split results are bit-identical to the separate GEMMs
        # (test_backend_dispatch.py asserts this) while the MXU sees
        # one big matmul.  concat_out fuses int8 QuantTensors the same
        # way (per-output-channel scales concatenate with the columns).
        w_qkv = qt.concat_out([p["w_q"], p["w_k"], p["w_v"]])
        qkv = qt.matmul(x, w_qkv)
        if cfg.attention_bias:
            qkv = qkv + jnp.concatenate([p["b_q"], p["b_k"], p["b_v"]])
        q, k, v = jnp.split(qkv, (cfg.q_dim, cfg.q_dim + cfg.kv_dim),
                            axis=-1)
    q = q.reshape(B, T, cfg.n_heads, cfg.head_dim)
    k = k.reshape(B, T, cfg.n_kv_heads, cfg.head_dim)
    v = v.reshape(B, T, cfg.n_kv_heads, cfg.head_dim)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    if rope:
        q = apply_rope(q, positions, cfg.rope_theta, cfg.partial_rotary_factor)
        k = apply_rope(k, positions, cfg.rope_theta, cfg.partial_rotary_factor)
    return q, k, v


def attention_forward(cfg: ModelConfig, p, x, positions, *,
                      causal: bool = True, window: int = 0,
                      rope: bool = True,
                      kv_len: Optional[jnp.ndarray] = None,
                      win_valid: Optional[jnp.ndarray] = None,
                      backend: Optional[str] = None) -> jnp.ndarray:
    """Full-sequence attention (training / prefill without cache reuse).

    ``backend`` selects the kernel backend (kernels.dispatch): window
    blocks route to the Pallas window-attention kernel, global blocks to
    the Pallas flash kernel; ``"xla"`` keeps the pure-jnp paths.

    Length-bucketed padded sequences thread their traced validity here:
    ``kv_len`` (B,) masks pad KEYS out of global attention (the sdpa
    masked path: the Pallas flash kernel's per-row lengths, or XLA's
    masked softmax), ``win_valid`` (B,) flags whole pad windows for
    window attention.
    """
    q, k, v = _project_qkv(cfg, p, x, positions, rope)
    if window > 0:
        out = window_sdpa(q, k, v, window, win_valid=win_valid,
                          backend=backend)
    else:
        out = sdpa(q, k, v, causal=causal, kv_len=kv_len, backend=backend)
    if _HEAD_TAP is not None and not isinstance(out, jax.core.Tracer):
        _HEAD_TAP.append(np.asarray(jnp.mean(
            jnp.abs(out.astype(jnp.float32)), axis=(0, 1, 3))))
    out = qt.matmul(out.reshape(x.shape[0], x.shape[1], cfg.q_dim),
                    p["w_o"])
    if cfg.attention_bias:
        out = out + p["b_o"]
    return out


def init_kv_cache(cfg: ModelConfig, batch: int, max_len: int, dtype):
    shape = (batch, max_len, cfg.n_kv_heads, cfg.head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def attention_prefill(cfg: ModelConfig, p, x, positions, cache, *,
                      rope: bool = True):
    """Prefill: run causal attention and write k/v into the cache at [0,T)."""
    q, k, v = _project_qkv(cfg, p, x, positions, rope)
    cache = {
        "k": jax.lax.dynamic_update_slice(cache["k"], k.astype(cache["k"].dtype),
                                          (0, 0, 0, 0)),
        "v": jax.lax.dynamic_update_slice(cache["v"], v.astype(cache["v"].dtype),
                                          (0, 0, 0, 0)),
    }
    out = sdpa(q, k, v, causal=True)
    out = qt.matmul(out.reshape(x.shape[0], x.shape[1], cfg.q_dim),
                    p["w_o"])
    if cfg.attention_bias:
        out = out + p["b_o"]
    return out, cache


def attention_decode(cfg: ModelConfig, p, x, pos, cache, *,
                     rope: bool = True):
    """One-token decode. x: (B,1,D); pos: scalar absolute position."""
    B = x.shape[0]
    positions = jnp.full((B, 1), pos)
    q, k, v = _project_qkv(cfg, p, x, positions, rope)
    cache = {
        "k": jax.lax.dynamic_update_slice(
            cache["k"], k.astype(cache["k"].dtype), (0, pos, 0, 0)),
        "v": jax.lax.dynamic_update_slice(
            cache["v"], v.astype(cache["v"].dtype), (0, pos, 0, 0)),
    }
    kv_len = jnp.full((B,), pos + 1)
    out = sdpa(q, cache["k"], cache["v"], kv_len=kv_len)
    out = qt.matmul(out.reshape(B, 1, cfg.q_dim), p["w_o"])
    if cfg.attention_bias:
        out = out + p["b_o"]
    return out, cache


# ---------------------------------------------------------------------------
# cross attention (whisper decoder)


def init_cross_attention(cfg: ModelConfig, key, dtype):
    ks = jax.random.split(key, 4)
    return {
        "w_q": dense_init(ks[0], (cfg.d_model, cfg.q_dim), dtype),
        "w_k": dense_init(ks[1], (cfg.d_model, cfg.kv_dim), dtype),
        "w_v": dense_init(ks[2], (cfg.d_model, cfg.kv_dim), dtype),
        "w_o": dense_init(ks[3], (cfg.q_dim, cfg.d_model), dtype),
    }


def cross_attention(cfg: ModelConfig, p, x, enc_out):
    B, T, _ = x.shape
    S = enc_out.shape[1]
    q = (x @ p["w_q"]).reshape(B, T, cfg.n_heads, cfg.head_dim)
    k = (enc_out @ p["w_k"]).reshape(B, S, cfg.n_kv_heads, cfg.head_dim)
    v = (enc_out @ p["w_v"]).reshape(B, S, cfg.n_kv_heads, cfg.head_dim)
    out = sdpa(q, k, v, causal=False)
    return out.reshape(B, T, cfg.q_dim) @ p["w_o"]


# ---------------------------------------------------------------------------
# Multi-head Latent Attention (DeepSeek-V2)
#
# Cache stores only the compressed latent c_kv (rank kv_lora) plus the
# decoupled RoPE key k_rope — the paper's point: tiny KV cache.  Decode
# uses the weight-absorption trick: q_nope is mapped through W_uk into
# latent space so attention scores are computed against c_kv directly.


def init_mla(cfg: ModelConfig, key, dtype):
    m = cfg.mla
    ks = jax.random.split(key, 8)
    qk_head = m.qk_nope_head_dim + m.qk_rope_head_dim
    return {
        "w_dq": dense_init(ks[0], (cfg.d_model, m.q_lora_rank), dtype),
        "q_norm": jnp.ones((m.q_lora_rank,), dtype),
        "w_uq": dense_init(ks[1], (m.q_lora_rank, cfg.n_heads * qk_head), dtype),
        "w_dkv": dense_init(ks[2], (cfg.d_model,
                                    m.kv_lora_rank + m.qk_rope_head_dim), dtype),
        "kv_norm": jnp.ones((m.kv_lora_rank,), dtype),
        "w_uk": dense_init(ks[3], (m.kv_lora_rank,
                                   cfg.n_heads * m.qk_nope_head_dim), dtype),
        "w_uv": dense_init(ks[4], (m.kv_lora_rank,
                                   cfg.n_heads * m.v_head_dim), dtype),
        "w_o": dense_init(ks[5], (cfg.n_heads * m.v_head_dim, cfg.d_model), dtype),
    }


def init_mla_cache(cfg: ModelConfig, batch: int, max_len: int, dtype):
    m = cfg.mla
    return {
        "c_kv": jnp.zeros((batch, max_len, m.kv_lora_rank), dtype),
        "k_rope": jnp.zeros((batch, max_len, m.qk_rope_head_dim), dtype),
    }


def _mla_q(cfg: ModelConfig, p, x, positions):
    m = cfg.mla
    B, T, _ = x.shape
    qk_head = m.qk_nope_head_dim + m.qk_rope_head_dim
    cq = rms_norm(x @ p["w_dq"], p["q_norm"], cfg.norm_eps)
    q = (cq @ p["w_uq"]).reshape(B, T, cfg.n_heads, qk_head)
    q_nope = q[..., :m.qk_nope_head_dim]
    q_rope = apply_rope(q[..., m.qk_nope_head_dim:], positions, cfg.rope_theta)
    return q_nope, q_rope


def _mla_latents(cfg: ModelConfig, p, x, positions):
    m = cfg.mla
    dkv = x @ p["w_dkv"]
    c_kv = rms_norm(dkv[..., :m.kv_lora_rank], p["kv_norm"], cfg.norm_eps)
    k_rope = dkv[..., m.kv_lora_rank:][:, :, None, :]    # single shared head
    k_rope = apply_rope(k_rope, positions, cfg.rope_theta)[:, :, 0, :]
    return c_kv, k_rope


def mla_forward(cfg: ModelConfig, p, x, positions, cache=None, pos=None):
    """MLA attention.  If ``cache`` is given with scalar ``pos`` -> decode;
    if cache given without pos -> prefill (writes [0,T)); else training."""
    m = cfg.mla
    B, T, _ = x.shape
    scale = (m.qk_nope_head_dim + m.qk_rope_head_dim) ** -0.5

    q_nope, q_rope = _mla_q(cfg, p, x, positions)
    decode = cache is not None and pos is not None
    c_new, kr_new = _mla_latents(cfg, p, x, positions)

    if cache is not None:
        off = pos if decode else 0
        cache = {
            "c_kv": jax.lax.dynamic_update_slice(
                cache["c_kv"], c_new.astype(cache["c_kv"].dtype), (0, off, 0)),
            "k_rope": jax.lax.dynamic_update_slice(
                cache["k_rope"], kr_new.astype(cache["k_rope"].dtype),
                (0, off, 0)),
        }
        c_kv, k_rope = cache["c_kv"], cache["k_rope"]
        S = c_kv.shape[1]
    else:
        c_kv, k_rope = c_new, kr_new
        S = T

    # absorb W_uk into the query:  score_nope = (q_nope @ W_uk^T) . c_kv
    w_uk = p["w_uk"].reshape(m.kv_lora_rank, cfg.n_heads, m.qk_nope_head_dim)
    w_uv = p["w_uv"].reshape(m.kv_lora_rank, cfg.n_heads, m.v_head_dim)
    c_kv32 = c_kv.astype(jnp.float32)
    k_rope32 = k_rope.astype(jnp.float32)

    def attend(qn_blk, qr_blk, off):
        """One q block (B, C, H, *) at absolute offset ``off``."""
        Tc = qn_blk.shape[1]
        q_lat = jnp.einsum("bthd,lhd->bthl", qn_blk.astype(jnp.float32),
                           w_uk.astype(jnp.float32))
        logits = (jnp.einsum("bthl,bsl->bhts", q_lat, c_kv32) +
                  jnp.einsum("bthd,bsd->bhts", qr_blk.astype(jnp.float32),
                             k_rope32)) * scale
        if decode:
            valid = jnp.arange(S) < (pos + 1)              # (S,)
            logits = jnp.where(valid[None, None, None, :], logits, NEG_INF)
        else:
            q_pos = jnp.arange(Tc) + off
            mask = q_pos[:, None] >= jnp.arange(S)[None, :]
            logits = jnp.where(mask[None, None], logits, NEG_INF)
        probs = jax.nn.softmax(logits, axis=-1)
        o_lat = jnp.einsum("bhts,bsl->bthl", probs, c_kv32)
        return jnp.einsum("bthl,lhd->bthd", o_lat, w_uv.astype(jnp.float32))

    if T > 2 * Q_CHUNK and T % Q_CHUNK == 0:
        nb = T // Q_CHUNK
        qn = jnp.moveaxis(q_nope.reshape(B, nb, Q_CHUNK, *q_nope.shape[2:]),
                          1, 0)
        qr = jnp.moveaxis(q_rope.reshape(B, nb, Q_CHUNK, *q_rope.shape[2:]),
                          1, 0)
        offs = jnp.arange(nb) * Q_CHUNK

        def body(_, inp):
            return None, attend(inp[0], inp[1], inp[2])

        from repro.models.layers import scan as _scan
        _, outs = _scan(body, None, (qn, qr, offs))
        out = jnp.moveaxis(outs, 0, 1).reshape(B, T, cfg.n_heads,
                                               m.v_head_dim)
    else:
        out = attend(q_nope, q_rope, 0)
    out = out.reshape(B, T, cfg.n_heads * m.v_head_dim).astype(x.dtype)
    out = out @ p["w_o"]
    return (out, cache) if cache is not None else out
