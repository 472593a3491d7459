"""ViTDet-style dense-prediction backbone with dynamic mixed-resolution
inference (the paper's case-study model, §III).

Structure (ViTDet, Li et al. 2022): ``n_layers`` pre-norm ViT blocks split
into N subsets of M blocks; within a subset the first M-1 blocks use
non-overlapping window attention, the last uses global attention.

Mixed-resolution inference: the image is packed into a window-blocked
mixed sequence (core.mixed_res).  At restoration point ``beta``:
  beta = 0          restore immediately after patch embedding (paper's
                    "Subset 0" special case — upsampled input);
  beta = k (1..N)   restore inside subset k (1-indexed), between its last
                    window block and its global block.
The output is always a full-resolution (B, Hp, Wp, D) feature map, so the
dense head is untouched — the paper's key compatibility property.

Simplification vs. the released ViTDet (recorded in DESIGN.md): no
relative-position bias inside attention (absolute learned pos-emb only).
"""
from __future__ import annotations

from collections import OrderedDict
from typing import Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import det_head as dh
from repro.core import mixed_res as mr
from repro.core.partition import (Partition, length_bucket as
                                  pt_length_bucket, make_partition)
from repro.kernels import dispatch
from repro.models import attention as attn
from repro.models import layers as L
from repro.models.config import ModelConfig
from repro.quant import qtensor as qt
from repro.spans import HEAD, POST_BETA, PRE_BETA


def vit_partition(cfg: ModelConfig) -> Partition:
    v = cfg.vit
    grid_h = v.img_size[0] // v.patch_size
    grid_w = v.img_size[1] // v.patch_size
    d = cfg.mixed_res.downsample if cfg.mixed_res else 2
    return make_partition(grid_h, grid_w, v.window_size, d)


def blocks_per_subset(cfg: ModelConfig) -> int:
    assert cfg.n_layers % cfg.vit.n_subsets == 0
    return cfg.n_layers // cfg.vit.n_subsets


# ---------------------------------------------------------------------------
# params


def init_vitdet_params(cfg: ModelConfig, key, dtype=jnp.float32) -> Dict:
    v = cfg.vit
    part = vit_partition(cfg)
    ks = jax.random.split(key, cfg.n_layers + 3)
    patch_dim = v.patch_size * v.patch_size * 3

    def block(k):
        kk = jax.random.split(k, 2)
        return {
            "ln1": L.init_norm(cfg, dtype),
            "attn": attn.init_attention(cfg, kk[0], dtype),
            "ln2": L.init_norm(cfg, dtype),
            "ffn": L.init_mlp(cfg, kk[1], dtype),
        }

    return {
        "patch_embed": {
            "w": L.dense_init(ks[0], (patch_dim, cfg.d_model), dtype),
            "b": jnp.zeros((cfg.d_model,), dtype),
        },
        "pos_emb": L.embed_init(ks[1], (part.grid_h, part.grid_w,
                                        cfg.d_model), dtype),
        "blocks": [block(ks[2 + i]) for i in range(cfg.n_layers)],
        "final_norm": L.init_norm(cfg, dtype),
        "head": dh.init_det_head(cfg, ks[-1], dtype),
    }


# ---------------------------------------------------------------------------
# patchify (conv-free: reshape + matmul, MXU-friendly)


def patchify(image: jnp.ndarray, patch: int) -> jnp.ndarray:
    """(B, H, W, 3) -> (B, H/p, W/p, p*p*3) raw patch grid."""
    B, H, W, C = image.shape
    x = image.reshape(B, H // patch, patch, W // patch, patch, C)
    x = x.transpose(0, 1, 3, 2, 4, 5)
    return x.reshape(B, H // patch, W // patch, patch * patch * C)


def embed_patches(cfg: ModelConfig, params, image: jnp.ndarray,
                  downsample: int = 1,
                  backend: Optional[str] = None) -> jnp.ndarray:
    """Patchify (optionally pixel-downsampled) image and project to D."""
    if downsample > 1:
        image = mr.downsample_grid(image, downsample, backend=backend)
    p = params["patch_embed"]
    patches = patchify(image, cfg.vit.patch_size)
    return qt.matmul(patches, p["w"]) + p["b"]


# ---------------------------------------------------------------------------
# packed positional-embedding cache
#
# pack_positions is pure data movement on the (trainable but
# inference-frozen) pos_emb grid; re-packing it inside every eager
# forward_features call is wasted work.  The cache key is
# (pos_emb identity, partition, layout fingerprint) and is bypassed
# whenever any input is a tracer (jit/grad see the uncached computation,
# so training and compiled paths are unaffected).  The fingerprint is
# ``ids_key`` when the caller precomputed one (PlanLayout.key — built
# ONCE at plan-layout time, so cache hits are O(1) with no host sync);
# legacy callers without a key fall back to hashing the id bytes per
# call (one d2h per array — the cost the serving hot path now avoids).


_POS_CACHE: "OrderedDict[tuple, tuple]" = OrderedDict()
_POS_CACHE_MAX = 64


def _concrete(*xs) -> bool:
    return not any(isinstance(x, jax.core.Tracer) for x in xs)


def _ids_fingerprint(*ids: jnp.ndarray) -> bytes:
    return b"|".join(np.ascontiguousarray(np.asarray(a)).tobytes()
                     for a in ids)


def packed_positions(pos: jnp.ndarray, part: Partition,
                     full_ids: Optional[jnp.ndarray],
                     low_ids: Optional[jnp.ndarray], *,
                     win_src: Optional[jnp.ndarray] = None,
                     ids_key: Optional[bytes] = None) -> jnp.ndarray:
    """Cached pack of the positional grid for the requested layout.

    full_ids/low_ids None and win_src None -> full-resolution
    window-blocked layout; (B, n) per-sample ids produce a
    (B, n_tokens, D) batch.  ``win_src`` selects the length-bucketed
    padded layout (mixed_res.pack_positions_padded) instead of the
    exact-shape one.  ``ids_key``: precomputed layout fingerprint for
    O(1) cache hits.
    """
    if win_src is not None:
        if not _concrete(pos, win_src):
            return mr.pack_positions_padded(pos, part, win_src)
        key = (id(pos), part, "padded", tuple(win_src.shape),
               ids_key if ids_key is not None
               else _ids_fingerprint(win_src))
    elif low_ids is not None:
        if not _concrete(pos, full_ids, low_ids):
            return mr.pack_positions(pos, part, full_ids, low_ids)
        key = (id(pos), part, tuple(low_ids.shape),
               ids_key if ids_key is not None
               else _ids_fingerprint(full_ids, low_ids))
    else:
        if not _concrete(pos):
            return mr.grid_to_full_seq(pos[None], part)[0]
        key = (id(pos), part, "full")

    hit = _POS_CACHE.get(key)
    # the cached entry pins ``pos`` so id() cannot be recycled; the
    # identity check guards against a stale module-level cache anyway.
    if hit is not None and hit[0] is pos:
        _POS_CACHE.move_to_end(key)
        return hit[1]
    if win_src is not None:
        packed = mr.pack_positions_padded(pos, part, win_src)
    elif low_ids is not None:
        packed = mr.pack_positions(pos, part, full_ids, low_ids)
    else:
        packed = mr.grid_to_full_seq(pos[None], part)[0]
    while len(_POS_CACHE) >= _POS_CACHE_MAX:
        _POS_CACHE.popitem(last=False)    # LRU: evict the oldest only
    _POS_CACHE[key] = (pos, packed)
    return packed


def pos_window_bank(pos: jnp.ndarray, part: Partition) -> jnp.ndarray:
    """Cached (nR*d^2 + nR, w^2, D) window bank of the positional grid —
    the fused pack/pos prologue gathers positions from this bank in the
    same pass as the activations, so the layout-dependent gather no
    longer needs a per-layout cache entry (one bank covers every plan).
    """
    if not _concrete(pos):
        return mr.window_bank(pos[None], part)[0]
    key = (id(pos), part, "bank")
    hit = _POS_CACHE.get(key)
    if hit is not None and hit[0] is pos:
        _POS_CACHE.move_to_end(key)
        return hit[1]
    bank = mr.window_bank(pos[None], part)[0]
    while len(_POS_CACHE) >= _POS_CACHE_MAX:
        _POS_CACHE.popitem(last=False)
    _POS_CACHE[key] = (pos, bank)
    return bank


# ---------------------------------------------------------------------------
# blocks


def _vit_block(cfg: ModelConfig, p, x, *, window: int,
               kv_len: Optional[jnp.ndarray] = None,
               win_valid: Optional[jnp.ndarray] = None,
               backend: Optional[str] = None) -> jnp.ndarray:
    """x: (B, T, D) window-blocked.  window=0 -> global attention.

    kv_len/win_valid: (B,) traced validity of a length-bucketed padded
    sequence — pad tokens are masked out of global attention keys, pad
    windows' window-attention outputs are zeroed.
    """
    B, T, D = x.shape
    h = L.apply_norm(cfg, p["ln1"], x)
    positions = jnp.zeros((B, T), jnp.int32)      # no RoPE in ViT
    a = attn.attention_forward(cfg, p["attn"], h, positions,
                               causal=False, window=window, rope=False,
                               kv_len=kv_len, win_valid=win_valid,
                               backend=backend)
    x = x + a
    h = L.apply_norm(cfg, p["ln2"], x)
    return x + L.apply_mlp(cfg, p["ffn"], h)


def _restore(cfg: ModelConfig, part: Partition, tokens, fused: bool,
             padded: bool, layout, full_ids, low_ids, reuse_ids,
             reuse_tiles, backend):
    """Restore the mixed sequence to full length at the restoration
    point, splicing REUSE regions from ``reuse_tiles``."""
    w2 = part.window * part.window
    if fused:
        B, D = tokens.shape[0], tokens.shape[-1]
        return dispatch.fused_restore(
            tokens.reshape(B, -1, w2, D), layout["out_src"],
            layout["out_map"], part.window, part.downsample,
            reuse_tiles=reuse_tiles)
    if padded:
        return mr.restore_padded(
            tokens, part, layout["win_dst"], layout["low_src"],
            layout["low_ids"], backend=backend,
            reuse_ids=(layout["reuse_ids"]
                       if reuse_tiles is not None else None),
            reuse_tiles=reuse_tiles)
    n_reuse = 0 if reuse_ids is None else reuse_ids.shape[-1]
    return mr.restore_full(
        tokens, part, full_ids, low_ids, backend=backend,
        reuse_ids=(reuse_ids if n_reuse else None),
        reuse_tiles=(reuse_tiles if n_reuse else None))


def forward_features(cfg: ModelConfig, params, image: jnp.ndarray,
                     full_ids: Optional[jnp.ndarray] = None,
                     low_ids: Optional[jnp.ndarray] = None,
                     beta: int = 0,
                     backend: Optional[str] = None,
                     reuse_ids: Optional[jnp.ndarray] = None,
                     reuse_tiles: Optional[jnp.ndarray] = None,
                     capture_beta: int = 0,
                     layout: Optional[Dict] = None,
                     ids_key: Optional[bytes] = None):
    """Backbone forward.  Returns the (B, Hp, Wp, D) full-res feature map
    (or ``(feats, tiles)`` when ``capture_beta > 0``, see below).

    full_ids/low_ids: static-length region id arrays (see core.partition),
    either (n,) shared across the batch or (B, n) per-sample (batched
    multi-client serving, serve/edge.py); None or empty low_ids -> plain
    full-resolution inference.
    beta: restoration point, 0..n_subsets (static).
    backend: kernel backend ("auto" | "pallas" | "xla", kernels.dispatch)
    for the window/global attention and pool/upsample hot paths.

    layout: the length-bucketed padded alternative to full_ids/low_ids
    (serving hot path): a dict of PlanLayout arrays — ``win_src`` /
    ``win_dst`` (·, nw_pad), ``low_src`` / ``low_ids`` / ``reuse_ids``
    (·, n_regions), ``nw`` valid window counts — each (n,) shared or
    (B, n) per-sample.  Shapes depend only on the LENGTH BUCKET; which
    regions are LOW/REUSE and how many windows are real is runtime i32
    data, so one executable serves every (n_low, n_reuse) mix.  Pad
    windows are masked out of pre-restoration global attention
    (``kv_len``), zeroed by window attention's per-window valid flag,
    and routed to the sentinel row at restoration — the valid prefix is
    bit-identical to the exact-shape forward (tests/test_padded_plans).
    Requires ``beta >= 1``; reuse regions splice from ``reuse_tiles``
    shaped (B, n_regions, d^2, w^2, D) (pad rows land on the sentinel).
    ids_key: optional precomputed layout fingerprint for the eager
    positional-embedding cache (PlanLayout.key).

    Temporal reuse (partition.RegionPlan):
    reuse_ids/reuse_tiles: regions ABSENT from the transmitted sequence,
    restored from cached per-region feature tiles
    ((B, n_reuse, d^2, w^2, D), captured by a previous forward at the
    SAME restoration point) — requires ``beta >= 1``.  None or empty
    reuse_ids leaves every code path bit-identical to the no-reuse call.
    capture_beta: when > 0, ALSO return the per-region feature tiles
    (B, n_regions, d^2, w^2, D) of the token state entering the global
    block of subset ``capture_beta`` — the tile the feature cache stores
    for the NEXT frame's reuse.  Must be >= beta when a restoration is
    pending (tiles are only defined on the full-length sequence); for a
    mixed forward ``capture_beta == beta`` captures the restored tensor
    itself, so reused regions' tiles round-trip unchanged (staleness is
    bounded by the policy's K, not by the cache).
    """
    part = vit_partition(cfg)
    v = cfg.vit
    M = blocks_per_subset(cfg)
    N = v.n_subsets
    w2 = part.window * part.window
    padded = layout is not None
    n_reuse = 0 if reuse_ids is None else reuse_ids.shape[-1]
    has_low = low_ids is not None and low_ids.shape[-1] > 0
    mixed = (padded and beta > 0) or ((has_low or n_reuse > 0)
                                      and beta > 0)
    assert 0 <= beta <= N
    assert 0 <= capture_beta <= N
    if padded:
        assert full_ids is None and low_ids is None and reuse_ids is None
        if beta == 0:
            # restore-at-input (paper's "Subset 0"): upsampled full-
            # length sequence from block 0 — REUSE tiles cannot splice
            # here (they are restoration-point features)
            assert reuse_tiles is None
    if n_reuse > 0:
        assert beta >= 1, "REUSE regions need a restoration point >= 1"
        assert reuse_tiles is not None
    if capture_beta and mixed:
        assert capture_beta >= beta, \
            "cannot capture tiles before the restoration point"

    # the device step in named scopes (HLO metadata only): vit.pre_beta
    # up to the restoration point, vit.post_beta from it on.  A
    # full-resolution forward splits at its capture point.
    split = beta if mixed else capture_beta
    with jax.named_scope(PRE_BETA):
        x_full = embed_patches(cfg, params, image,
                               backend=backend)         # B,Hp,Wp,D
        pos = qt.asarray(params["pos_emb"])
        kv_len = win_valid = None
        # fused serving lane (kernels.fused_serving): pack + pos-embed +
        # pad zeroing fold into one prologue kernel and the restoration
        # scatter into one destination-major gather epilogue, so the packed
        # activations never round-trip HBM between the stages.  Engages on
        # the Pallas backend when the layout carries the inverse maps
        # (PlanLayout.out_src/out_map); legacy layout dicts fall back to the
        # unfused path unchanged.
        fused = (padded and beta >= 1 and "out_src" in layout
                 and dispatch.use_pallas(backend))
        if padded:
            # the collapsed executable serves every plan mix, so the pooled
            # grid is always packed (a reuse-only sample simply never
            # gathers from the low half of the window bank)
            x_low = embed_patches(cfg, params, image, part.downsample, backend)
            if fused:
                # pad windows come out zero rather than window-0 replicas:
                # window attention zeroes them anyway, global attention
                # masks them via kv_len, restoration never reads them — the
                # valid lanes are bit-identical to the unfused pack
                bank = mr.window_bank(x_full, part, x_low, backend=backend)
                tokens = dispatch.fused_pack_pos(bank,
                                                 pos_window_bank(pos, part),
                                                 layout["win_src"],
                                                 layout["nw"])
            else:
                tokens = mr.pack_padded(x_full, part, layout["win_src"],
                                        x_low_grid=x_low, backend=backend)
            if beta == 0:                     # restore at input: full length
                tokens = mr.restore_padded(tokens, part, layout["win_dst"],
                                           layout["low_src"],
                                           layout["low_ids"],
                                           backend=backend)
                tokens = tokens + packed_positions(pos, part, None, None)
            else:
                if not fused:
                    tokens = tokens + packed_positions(
                        pos, part, None, None, win_src=layout["win_src"],
                        ids_key=ids_key)
                win_valid = jnp.asarray(layout["nw"], jnp.int32)
                kv_len = win_valid * w2
                if win_valid.ndim == 0:
                    win_valid = win_valid[None]
                    kv_len = kv_len[None]
        elif mixed:
            # reuse-only plans (n_low = 0) never read the pooled grid — skip
            # the downsampled patch-embedding pass entirely
            x_low = (embed_patches(cfg, params, image, part.downsample,
                                   backend) if has_low else None)
            tokens, _ = mr.pack_mixed(x_full, part, full_ids, low_ids,
                                      x_low_grid=x_low, backend=backend)
            tokens = tokens + packed_positions(pos, part, full_ids, low_ids,
                                               ids_key=ids_key)
        else:
            if has_low:                                           # beta == 0
                x_low = embed_patches(cfg, params, image, part.downsample,
                                      backend)
                packed, _ = mr.pack_mixed(x_full, part, full_ids, low_ids,
                                          x_low_grid=x_low, backend=backend)
                tokens = mr.restore_full(packed, part, full_ids, low_ids,
                                         backend=backend)
            else:
                tokens = mr.grid_to_full_seq(x_full, part)
            tokens = tokens + packed_positions(pos, part, None, None)

    tiles = None
    restored = not mixed
    for s in range(N):
        for m in range(M):
            idx = s * M + m
            params_blk = params["blocks"][idx]
            is_global = m == M - 1
            stage = PRE_BETA if idx < split * M - 1 else POST_BETA
            if is_global and not restored and beta == s + 1:
                with jax.named_scope(stage), jax.named_scope("restore"):
                    tokens = _restore(cfg, part, tokens, fused, padded,
                                      layout, full_ids, low_ids, reuse_ids,
                                      reuse_tiles, backend)
                restored = True
            if is_global and capture_beta == s + 1:
                B = tokens.shape[0]
                tiles = tokens.reshape(B, part.n_regions,
                                       part.windows_per_full_region,
                                       w2, tokens.shape[-1])
            with jax.named_scope(stage), jax.named_scope(f"block{idx:02d}"):
                tokens = _vit_block(cfg, params_blk, tokens,
                                    window=0 if is_global else w2,
                                    kv_len=None if restored else kv_len,
                                    win_valid=(None if restored
                                               else win_valid),
                                    backend=backend)
    # beta <= N always restores: beta == N hits the LAST global block.

    with jax.named_scope(POST_BETA):
        tokens = L.apply_norm(cfg, params["final_norm"], tokens)
        feats = mr.full_seq_to_grid(tokens, part)
    if capture_beta:
        return feats, tiles
    return feats


def forward_det(cfg: ModelConfig, params, image,
                full_ids=None, low_ids=None, beta: int = 0,
                backend: Optional[str] = None,
                reuse_ids=None, reuse_tiles=None, capture_beta: int = 0,
                layout: Optional[Dict] = None,
                ids_key: Optional[bytes] = None):
    """Full model: backbone + dense head.  Returns det_head outputs (or
    ``(outputs, tiles)`` when ``capture_beta > 0`` — the per-region
    restoration-point feature tiles that refresh the client's
    FeatureCache for temporal reuse).  ``layout`` selects the
    length-bucketed padded forward (see forward_features)."""
    feats = forward_features(cfg, params, image, full_ids, low_ids, beta,
                             backend=backend, reuse_ids=reuse_ids,
                             reuse_tiles=reuse_tiles,
                             capture_beta=capture_beta, layout=layout,
                             ids_key=ids_key)
    if capture_beta:
        feats, tiles = feats
    with jax.named_scope(HEAD):
        outs = dh.det_head_forward(cfg, params["head"], feats)
    return (outs, tiles) if capture_beta else outs


# ---------------------------------------------------------------------------
# FLOP accounting (used by the latency model and Fig. 5 benchmark)


def backbone_flops_windows(cfg: ModelConfig, n_windows: int,
                           beta: int) -> float:
    """Analytic attention+MLP FLOPs with the PRE-restoration sequence
    pinned to ``n_windows`` windows (``n_windows * w^2`` tokens) — the
    cost of a length-bucketed padded forward, where pad windows are
    masked but still computed.  ``beta == 0`` or a full-length
    ``n_windows`` degenerates to the plain full-resolution cost.
    """
    part = vit_partition(cfg)
    D, F = cfg.d_model, cfg.d_ff
    M = blocks_per_subset(cfg)
    N = cfg.vit.n_subsets
    w2 = part.window * part.window

    n_mixed = n_windows * w2
    n_full = part.grid_h * part.grid_w
    nw_full = part.n_regions * part.windows_per_full_region

    def block_flops(n_tok, n_win):
        proj = 4 * 2 * n_tok * D * D                     # qkvo projections
        if n_win:                                        # window attention
            att = 2 * 2 * n_win * w2 * w2 * D
        else:                                            # global attention
            att = 2 * 2 * n_tok * n_tok * D
        mlp = 2 * 2 * n_tok * D * F
        return proj + att + mlp

    total = 0.0
    restored = beta <= 0
    for s in range(N):
        for m in range(M):
            is_global = m == M - 1
            if is_global and not restored and beta == s + 1:
                restored = True
            if restored:
                total += block_flops(n_full, 0 if is_global else nw_full)
            else:
                total += block_flops(n_mixed, 0 if is_global else n_windows)
    return total


def backbone_flops(cfg: ModelConfig, n_low: int, beta: int,
                   n_reuse: int = 0,
                   length_edges: Optional[Sequence[int]] = None) -> float:
    """Analytic attention+MLP FLOPs of the backbone for a given config.

    Mirrors forward_features' block schedule; used to parameterise the
    inference-delay linear models LM^inf_beta(N_d, N_r) (paper §IV-D,
    extended with the temporal-reuse term: reused regions contribute NO
    tokens before the restoration point).

    ``length_edges``: cost the PADDED length bucket the serving hot path
    actually runs (partition.length_bucket_set) instead of the exact
    mixed length — what LM^inf must model once executables are keyed on
    length buckets rather than (n_low, n_reuse).
    """
    part = vit_partition(cfg)
    mixed = (n_low > 0 or n_reuse > 0) and beta > 0
    if not mixed:
        return backbone_flops_windows(
            cfg, part.n_regions * part.windows_per_full_region, 0)
    nw = part.n_windows(n_low, n_reuse)
    if length_edges is not None:
        # the degenerate all-reuse point (0 transmitted windows) is not
        # servable (policies keep >= 1 transmitted region) but delay-
        # model fits probe it — cost it at the smallest bucket
        nw = pt_length_bucket(max(nw, 1), length_edges)
    return backbone_flops_windows(cfg, nw, beta)
