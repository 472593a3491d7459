"""Operations and bytes the served work needs, from shapes alone, and the
chips' published peaks.

``backbone_flops_windows`` is the arithmetic of
``core.vit_backbone.backbone_flops_windows`` (attention + MLP matmul
FLOPs of the block schedule with the pre-restoration sequence pinned to
a window count), kept here so that the yardstick cannot move with the
program; a test holds the two equal.
"""
from __future__ import annotations

from typing import Dict

# Published per-chip peaks, keyed by ``jax.Device.device_kind``.
# Source: Google Cloud documentation, "TPU v5e" (system architecture):
# 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s.
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9},
}


def peaks(device_kind: str) -> Dict[str, float]:
    """The peaks of a device kind; an unknown kind is an error."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; add them to PEAKS with their "
                       f"source") from None


def _geometry(c: Dict):
    grid = c["img_size"] // c["patch_size"]
    w, d = c["window_size"], c["downsample"]
    n_regions = (grid // (w * d)) ** 2
    return grid, w * w, n_regions, d * d


def backbone_flops_windows(c: Dict, n_windows: int, beta: int) -> float:
    """Attention + MLP FLOPs of one frame whose pre-restoration sequence
    holds ``n_windows`` windows (``beta`` = restoration subset, 0 for a
    full-resolution frame)."""
    grid, w2, nR, dd = _geometry(c)
    D, F = c["d_model"], c["d_ff"]
    N = c["n_subsets"]
    M = c["n_layers"] // N
    n_full = grid * grid
    nw_full = nR * dd

    def block(n_tok, n_win):
        proj = 4 * 2 * n_tok * D * D
        att = (2 * 2 * n_win * w2 * w2 * D if n_win
               else 2 * 2 * n_tok * n_tok * D)
        return proj + att + 2 * 2 * n_tok * D * F

    total = 0.0
    restored = beta <= 0
    for s in range(N):
        for m in range(M):
            is_global = m == M - 1
            if is_global and not restored and beta == s + 1:
                restored = True
            if restored:
                total += block(n_full, 0 if is_global else nw_full)
            else:
                total += block(n_windows * w2, 0 if is_global
                               else n_windows)
    return total


def plan_windows(c: Dict, n_low: int, n_reuse: int) -> int:
    """Transmitted windows of a plan (its real, unpadded length)."""
    _, _, nR, dd = _geometry(c)
    return (nR - n_low - n_reuse) * dd + n_low


def frame_flops(c: Dict, n_low: int, n_reuse: int, beta: int) -> float:
    """Backbone FLOPs of one served frame at its real window count."""
    _, _, nR, dd = _geometry(c)
    if n_low == 0 and n_reuse == 0:
        return backbone_flops_windows(c, nR * dd, 0)
    return backbone_flops_windows(c, plan_windows(c, n_low, n_reuse), beta)


# ---------------------------------------------------------------------------
# attention kernels: one call's FLOPs and least bytes moved


def attention_call(groups: int, heads: int, tokens: int, head_dim: int,
                   itemsize: int = 4) -> Dict[str, float]:
    """One attention-kernel call: ``groups`` sequences (a batch of
    frames, or of windows) of ``tokens`` tokens that attend within their
    sequence, ``heads`` heads of ``head_dim``.  FLOPs count QK^T and PV
    (2 * 2 * tokens^2 * head_dim per head and sequence); bytes count
    reading q, k, v and writing the output once."""
    flops = 2.0 * 2.0 * groups * heads * tokens * tokens * head_dim
    bytes_ = 4.0 * itemsize * groups * heads * tokens * head_dim
    return {"flops": flops, "bytes": bytes_}


def roofline_seconds(work: Dict[str, float], pk: Dict[str, float]) -> float:
    """Least time for ``work`` on a chip: the larger of its FLOPs at the
    bf16 peak and its bytes at the HBM peak."""
    return max(work["flops"] / pk["bf16_flops"],
               work["bytes"] / pk["hbm_bytes_per_s"])
