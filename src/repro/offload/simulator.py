"""Event-driven end-to-end MVA offloading simulator (paper §VI).

Replays a synthetic video at a fixed FPS against a network trace.  The
device side (motion analysis, tracking, estimation, Algorithm 1) runs for
real; the server side runs the actual mixed-resolution ViTDet model
(trained on the synthetic domain) on the codec-decoded frame; delays
follow Eq. (2) with the inference term calibrated to the paper's measured
ViTDet-L numbers (281 ms @ full-res 1080p on the RTX 5090 — DESIGN.md).

Rendering accuracy is the F1 between what the user SEES (cache or tracker
output) and the ground truth of the CURRENT frame, where ground truth =
the full-resolution model output (exactly the paper's metric).
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import spans
from repro.core import mixed_res as mr
from repro.core import partition as pt
from repro.core import vit_backbone as vb
from repro.core.partition import LOW, REUSE, Partition, RegionPlan
from repro.kernels import autotune, dispatch
from repro.models import registry
from repro.quant import qtensor as qt
from repro.models.config import ModelConfig
from repro.offload import detection as det
from repro.offload import motion as mo
from repro.offload.codec import CodecDelayModel, MixedResCodec
from repro.offload.estimator import ThroughputEstimator
from repro.offload.faults import (DegradationLadder, FaultInjector,
                                  RobustConfig, fresh_rstats)
from repro.offload.optimizer import OffloadConfig, SystemState
from repro.offload.tracker import LKTracker
from repro.serve.request import (FeatureCache, ServingStats,
                                 StaleCacheEpoch)
from repro.serve.scheduler import SoloScheduler

# payload scale: our 512x512 luma codec vs the paper's 1080p YUV frames
SIZE_SCALE = (1920 * 1080) / (512 * 512)


# ---------------------------------------------------------------------------
# server model wrapper — the length-bucketed serving hot path.
#
# EVERY inference (solo N=1, batched multi-client wave, padded or
# coalesced) runs through ONE code path, infer_wave: mask-traced padded
# plan layouts (core.partition.PlanLayout), the wave padded UP to a
# batch bucket, against an AOT-compiled executable keyed on the
# collapsed grid
#     (length bucket, beta, capture point, B bucket).
# (n_low, n_reuse) are runtime i32 DATA, not shape — any plan mix at one
# length bucket shares one executable.  warmup() compiles the grid off
# the critical path at replica start; after it, a steady-state compile
# is telemetry (stats.steady_compiles) that tests and bench_serving
# treat as a failure.

# argument order of a mixed executable's layout arrays
_LAYOUT_ARGS = ("win_src", "win_dst", "low_src", "low_ids", "reuse_ids",
                "nw", "out_src", "out_map")


@dataclass
class StagedWave:
    """A wave's decoded frames, already padded to their B bucket and
    shipped to the device (:meth:`ServerModel.stage_frames`) — the h2d
    of wave N+1 overlaps wave N's compute under JAX async dispatch."""
    B: int                   # real rows; imgs carries Bp >= B
    imgs: jnp.ndarray
    wave: Optional[spans.Wave] = None     # while spans are recorded


@dataclass
class PendingWave:
    """An in-flight wave result: the forward has been DISPATCHED but
    the blocking host-side detection decode has not run.  The scheduler
    defers :meth:`wait` until the next wave is on the device, so host
    decode hides under device compute."""
    boxes: jnp.ndarray
    scores: jnp.ndarray
    classes: jnp.ndarray
    B: int
    score_thresh: float
    wave: Optional[spans.Wave] = None     # while spans are recorded

    def wait(self) -> List[List[Dict]]:
        # Each row and array is read through a row slice (dynamic_slice
        # and squeeze: two device computations).  The first row's slices
        # are dispatched before anything blocks: slices dispatched after
        # a block on the whole outputs queue behind any wave launched
        # since, and the reads then wait out its forward too.
        with spans.span("serve.wait", self.wave):
            with spans.span("serve.ready") as sp:
                first = (self.boxes[0], self.scores[0], self.classes[0])
                jax.block_until_ready(first)
                sp.add("jit_launches", 6)
            with spans.span("serve.decode") as sp:
                dets = [det.detections_from_arrays(*first,
                                                   self.score_thresh)]
                dets += [det.detections_from_arrays(
                             self.boxes[i], self.scores[i], self.classes[i],
                             self.score_thresh)
                         for i in range(1, self.B)]
                sp.add("jit_launches", 6 * (self.B - 1))
                sp.add("d2h", 3 * self.B)
        return dets


class ServerModel:
    """Server-side detector with an AOT-compiled length-bucketed
    executable grid and device-resident feature caches.

    The transmitted window count of a plan is rounded UP to a
    ``length_edges`` bucket (partition.length_bucket_set); WHICH regions
    are LOW/REUSE and how many windows are real travel as runtime i32
    inputs (PlanLayout), so executables are keyed on
    ``(length bucket, beta, capture, B bucket)`` only — ~len(edges)+1
    executables per (beta, B) instead of one per (n_low, n_reuse)
    bucket pair, and waves may mix arbitrary (n_low, n_reuse) plans at
    one length bucket.  Mixed executables always capture restoration-
    point tiles (capture == beta; callers without a session drop them)
    and full-res executables capture at the deployment's canonical
    ``full_capture`` point, so sessionful and stateless traffic share
    one grid.  Wave sizes are padded UP to ``b_buckets`` edges with
    copies of sample 0; padded rows are dropped from the decoded
    detections and never touch a FeatureCache, so within one executable
    the padding is bit-invisible (pinned by tests).

    ``device_cache=True`` keeps captured restoration-point tiles as
    device arrays end to end: reuse gathers and cache refreshes are
    jitted index ops (core.mixed_res) and ship ZERO tile bytes between
    host and device per offload (stats.tile_bytes_*); ``False`` is the
    legacy host-resident mode the bench compares against.

    ``backend`` selects the kernel backend for the backbone hot path
    (kernels.dispatch: "auto" | "pallas" | "xla").  ``jit=False`` runs
    the forward eagerly (op-by-op) — only useful to benchmark what the
    bucketed cache buys (benchmarks/bench_backbone.py quotes both).
    """

    def __init__(self, cfg: ModelConfig, params, top_k: int = 32,
                 score_thresh: float = 0.4,
                 backend: Optional[str] = "auto", jit: bool = True,
                 n_buckets: int = 4,
                 b_buckets: Tuple[int, ...] = pt.BATCH_BUCKETS,
                 device_cache: bool = True,
                 n_length_buckets: int = pt.N_LENGTH_BUCKETS,
                 quant=None, calib_frames=None):
        # ``quant``: optional quant.ptq.QuantSpec — compress the float
        # tree (int8 / half-cast / head-pruned) BEFORE any executable is
        # built, so the whole grid compiles against the compressed
        # params and the grid keys never change.  ``calib_frames`` feeds
        # head scoring when the spec prunes.  Pre-compressed trees (the
        # calibration gate builds candidates itself) pass quant=None.
        self.quant_report = None
        if quant is not None:
            from repro.quant import ptq
            cfg, params, self.quant_report = ptq.compress(
                cfg, params, quant, calib_frames=calib_frames)
        self.cfg = cfg
        self.params = params
        # activation dtype of the serving grid.  Detected from the tree
        # rather than a spec so pre-compressed params work: cast_tree
        # always casts the patch-embed bias (QuantTensor scales stay f32
        # and are useless as a probe), and activations take this dtype
        # at the very first matmul.
        self.act_dtype = jnp.dtype(params["patch_embed"]["b"].dtype)
        self.part = vb.vit_partition(cfg)
        self.top_k = top_k
        self.score_thresh = score_thresh
        self.backend = backend
        self.jit = jit
        self.n_buckets = n_buckets
        self.b_buckets = tuple(sorted(b_buckets))
        self.device_cache = device_cache
        self.length_edges = pt.length_bucket_set(self.part,
                                                 n_length_buckets)
        # canonical capture point of the FULL-RES executable: sessions
        # bootstrap with full-res offloads that capture tiles at their
        # beta; stateless callers share that executable and drop the
        # tiles, so capture never fragments the grid (set by warmup)
        self.full_capture = 0
        self._fns: Dict[Tuple[int, int, int, int], Callable] = {}
        self._zero_tiles: Dict[int, jnp.ndarray] = {}
        self.stats = ServingStats()
        # cache generation: bumped by restart(); FeatureCache tiles
        # captured under an older epoch can never be spliced again
        self.epoch = 0

    def restart(self, preserve_executables: bool = False) -> int:
        """Crash-restart this replica.

        Bumps the cache epoch — every FeatureCache tile captured before
        this moment belongs to a dead replica and any REUSE plan still
        carrying it is refused (StaleCacheEpoch) — and, unless
        ``preserve_executables`` (a bench shortcut when the outage is
        modelled in sim time only), wipes the warmed executable grid so
        the new process must re-warm (compiles count toward warmup
        again, not steady-state stalls).  Returns the new epoch.
        """
        self.epoch += 1
        self.stats.restarts += 1
        if not preserve_executables:
            self._fns.clear()
            self._zero_tiles.clear()
            self.stats.warmed = False
        return self.epoch

    def bucket(self, n_low: int) -> int:
        """Legacy policy-side n_low bucket (plan EMISSION still rounds
        down; the executable grid no longer keys on it)."""
        return pt.bucket_n_low(n_low, self.part.n_regions, self.n_buckets)

    def batch_bucket(self, b: int) -> int:
        return pt.batch_bucket(b, self.b_buckets)

    def length_bucket(self, n_windows: int) -> int:
        return pt.length_bucket(n_windows, self.length_edges)

    def plan_length_bucket(self, plan: RegionPlan) -> int:
        """The length bucket a plan's transmitted windows land in
        (0 = the dedicated full-resolution executable)."""
        if plan.n_low == 0 and plan.n_reuse == 0:
            return 0
        return self.length_bucket(pt.plan_n_windows(plan, self.part))

    def _decode(self, outs):
        from repro.core import det_head as dh
        return dh.decode_detections(self.cfg, outs, self.top_k,
                                    self.score_thresh)

    # ------------------------------------------------------------------
    # executable grid

    def _build_fn(self, lb: int, beta: int, capture: int) -> Callable:
        cfg, backend = self.cfg, self.backend

        def finish(outs):
            tiles = None
            if capture:
                outs, tiles = outs
            with jax.named_scope(spans.HEAD):
                dets = self._decode(outs)
            return (dets, tiles) if capture else dets

        if lb == 0:
            def fn(params, img):
                return finish(vb.forward_det(cfg, params, img,
                                             backend=backend,
                                             capture_beta=capture))
        else:
            def fn(params, img, win_src, win_dst, low_src, low_ids,
                   reuse_ids, nw, out_src, out_map, reuse_tiles,
                   ids_key=None):
                layout = {"win_src": win_src, "win_dst": win_dst,
                          "low_src": low_src, "low_ids": low_ids,
                          "reuse_ids": reuse_ids, "nw": nw,
                          "out_src": out_src, "out_map": out_map}
                # beta == 0 restores at input — reuse tiles are
                # restoration-point features and cannot splice there
                # (infer_wave bars reuse plans from beta=0 waves)
                return finish(vb.forward_det(
                    cfg, params, img, beta=beta, backend=backend,
                    layout=layout,
                    reuse_tiles=reuse_tiles if beta >= 1 else None,
                    capture_beta=capture, ids_key=ids_key))
        return fn

    def _arg_structs(self, lb: int, batch: int) -> List:
        """ShapeDtypeStructs of one executable's data arguments — shapes
        depend only on (length bucket, B bucket)."""
        part = self.part
        H, W = self.cfg.vit.img_size
        sds = [jax.ShapeDtypeStruct((batch, H, W, 3), jnp.float32)]
        if lb > 0:
            nR = part.n_regions
            sds.append(jax.ShapeDtypeStruct((batch, lb), jnp.int32))
            sds.append(jax.ShapeDtypeStruct((batch, lb), jnp.int32))
            for _ in ("low_src", "low_ids", "reuse_ids"):
                sds.append(jax.ShapeDtypeStruct((batch, nR), jnp.int32))
            sds.append(jax.ShapeDtypeStruct((batch,), jnp.int32))
            for _ in ("out_src", "out_map"):
                sds.append(jax.ShapeDtypeStruct(
                    (batch, nR * part.windows_per_full_region), jnp.int32))
            sds.append(jax.ShapeDtypeStruct(
                (batch, nR, part.windows_per_full_region,
                 part.tokens_low_region, self.cfg.d_model),
                self.act_dtype))
        return sds

    def _get_fn(self, lb: int, beta: int, capture: int = 0,
                batch: int = 1) -> Callable:
        key = (lb, beta, capture, batch)
        if key not in self._fns:
            fn = self._build_fn(lb, beta, capture)
            if self.jit:
                # AOT: lower + compile against the key's exact shapes.
                # The executable can never silently retrace, so each
                # cache miss is exactly one XLA compile — the telemetry
                # below is the whole compile surface.  One HLO module
                # name per key, so a device trace's module events say
                # which executable ran (spans.scope_map).
                name = f"serve_lb{lb}_beta{beta}_cap{capture}_b{batch}"
                fn.__name__ = fn.__qualname__ = name
                t0 = time.perf_counter()
                fn = jax.jit(fn).lower(
                    self.params, *self._arg_structs(lb, batch)).compile()
                self.stats.note_compile(key, time.perf_counter() - t0)
                spans.note_executable(name, fn)
            self._fns[key] = fn
        return self._fns[key]

    def _exec_key(self, n_low: int, n_reuse: int, beta: int,
                  cap: int) -> Tuple[int, int, int]:
        """Collapse a legacy (n_low, n_reuse, beta, capture) plan shape
        onto the (length bucket, beta, capture) executable it runs on.
        Full-res entries canonicalise through :meth:`_full_cap`, so a
        deployment that really configures several distinct full-res
        capture points warms each of them (no-capture requests fold
        into ``full_capture``)."""
        if n_low == 0 and n_reuse == 0:
            return (0, 0, self._full_cap(cap))
        lb = self.length_bucket(self.part.n_windows(n_low, n_reuse))
        return (lb, beta, beta)

    def warmup(self, plan_space, batch_buckets: Optional[Tuple[int, ...]]
               = None) -> int:
        """AOT-compile the executable grid off the critical path.

        ``plan_space``: iterable of (n_low, n_reuse, beta, capture
        point) tuples — the plan shapes the deployment's config space
        can emit (see :meth:`default_plan_space`).  The space is
        COLLAPSED onto the (length bucket, beta, capture, B bucket)
        grid: every (n_low, n_reuse) pair maps to its padded length
        bucket, mixed captures canonicalise to beta, and full-res
        captures to the deployment-wide ``full_capture`` point.  Each
        surviving key is compiled for every batch bucket.  Returns the
        number of executables compiled; afterwards
        ``stats.steady_compiles`` counts every further compile (a
        steady-state stall).
        """
        t0 = time.perf_counter()
        before = self.stats.compiles
        if dispatch.use_pallas(self.backend):
            # sweep Pallas block sizes for the grid's attention shapes
            # before any executable is traced, so the tuned winners are
            # baked into the compiled graphs (no-op off-TPU or with
            # REPRO_AUTOTUNE=0; later processes hit the disk cache)
            self._autotune_kernels(batch_buckets or self.b_buckets)
        space = dict.fromkeys(tuple(p) for p in plan_space)
        self.full_capture = max(
            [self.full_capture] + [cap for (n_low, n_reuse, _, cap)
                                   in space
                                   if n_low == 0 and n_reuse == 0])
        keys = dict.fromkeys(self._exec_key(n_low, n_reuse, beta, cap)
                             for (n_low, n_reuse, beta, cap) in space)
        for (lb, beta, cap) in keys:
            for b in (batch_buckets or self.b_buckets):
                self._get_fn(lb, beta, cap, b)
        if self.device_cache:
            self._warm_tile_ops(space, batch_buckets or self.b_buckets)
        return self.stats.finish_warmup(t0, before, time.perf_counter())

    def _autotune_kernels(self, batch_buckets) -> None:
        """Autotune window/flash block sizes for every (B bucket, length
        bucket) attention shape the executable grid can run."""
        part, cfg = self.part, self.cfg
        w2 = part.window * part.window
        T_full = part.grid_h * part.grid_w
        dt = self.act_dtype          # per-dtype buckets: an fp16 grid
        for b in batch_buckets:      # must never reuse fp32 winners
            for lb in self.length_edges:
                autotune.tune_window(b, lb * w2, cfg.n_heads,
                                     cfg.head_dim, w2, dtype=dt)
            autotune.tune_window(b, T_full, cfg.n_heads, cfg.head_dim,
                                 w2, dtype=dt)
            autotune.tune_flash(b, T_full, T_full, cfg.n_heads,
                                cfg.head_dim, dtype=dt)
        if any(isinstance(l, qt.QuantTensor)
               for l in jax.tree_util.tree_leaves(
                   self.params,
                   is_leaf=lambda x: isinstance(x, qt.QuantTensor))):
            # int8 lane: sweep the GEMM blocks for the grid's matmul
            # shapes (fused QKV / w_o / MLP at every sequence length)
            qkv_n = cfg.q_dim + 2 * cfg.kv_dim
            shapes = {(cfg.d_model, qkv_n), (cfg.q_dim, cfg.d_model),
                      (cfg.d_model, cfg.d_ff), (cfg.d_ff, cfg.d_model)}
            for b in batch_buckets:
                for T in {T_full} | {lb * w2 for lb in self.length_edges}:
                    for (K, N) in shapes:
                        autotune.tune_matmul(b * T, N, K,
                                             out_dtype=self.act_dtype)

    def _warm_tile_ops(self, space, batch_buckets) -> None:
        """Compile the device-resident cache's jitted index ops
        (mixed_res.gather_tiles / take_sample_tiles / refresh_tiles) for
        every tile shape the plan space can produce — they sit on the
        serving critical path too, and an unwarmed jit there would be a
        steady-state stall invisible to ``stats`` (jax.jit caches are
        global per function + shape, so one warm call covers serving)."""
        part = self.part
        tile = (part.n_regions, part.windows_per_full_region,
                part.tokens_low_region, self.cfg.d_model)
        dummy = jnp.zeros(tile, self.act_dtype)
        if any(n_reuse for (_, n_reuse, _, _) in space):
            # reuse gathers are (n_regions,)-padded — one shape for all
            mr.gather_tiles(dummy, jnp.zeros((part.n_regions,),
                                             jnp.int32))
        if any(cap for (_, _, _, cap) in space) or \
                any(n_low or n_reuse for (n_low, n_reuse, _, _) in space):
            # mixed executables always capture, so take/refresh are hot
            mr.refresh_tiles(jnp.zeros(tile, self.act_dtype), dummy)
            for b in batch_buckets:
                mr.take_sample_tiles(
                    jnp.zeros((b,) + tile, self.act_dtype), np.int32(0))

    def default_plan_space(self, betas: Sequence[int],
                           reuse_edges: Sequence[int] = (0,),
                           captures: Sequence[int] = (0,),
                           full_res: bool = True) -> List[Tuple[int, int,
                                                                int, int]]:
        """The plan grid a config space induces: every n_low bucket edge
        x n_reuse edge x beta x capture point.  Mixed plans capture at
        their own beta when the session captures at all (``captures``
        lists the extra full-res capture points).  :meth:`warmup`
        collapses this onto the (length bucket, beta, capture, B)
        executable grid."""
        edges = pt.bucket_set(self.part.n_regions, self.n_buckets)
        space: List[Tuple[int, int, int, int]] = []
        if full_res:
            for cap in captures:
                space.append((0, 0, 0, cap))
        for beta in betas:
            if beta < 1:
                continue
            for n_low in edges:
                for n_reuse in reuse_edges:
                    if n_low + n_reuse > self.part.n_regions:
                        continue
                    if n_low == 0 and n_reuse == 0:
                        continue
                    caps = {0}
                    if any(c > 0 for c in captures) or n_reuse > 0:
                        caps.add(beta)        # sessions capture at beta
                    for cap in sorted(caps):
                        if n_reuse > 0 and cap == 0:
                            continue          # reuse implies a session
                        space.append((n_low, n_reuse, beta, cap))
        return list(dict.fromkeys(space))

    # ------------------------------------------------------------------
    # the one serving entry point

    def _full_cap(self, want: int) -> int:
        """Canonical capture point of the full-res executable: requests
        for no capture (or the deployment's point) share ``full_capture``
        and simply drop the tiles."""
        if want == 0 or want == self.full_capture:
            return self.full_capture
        return want

    def stage_frames(self, frames: np.ndarray) -> StagedWave:
        """Asynchronously stage a wave's decoded frames on the device.

        Pads up to the B bucket on the host, then ``jax.device_put``
        enqueues the h2d copy WITHOUT blocking — called while the
        previous wave still computes, the transfer overlaps it.  The
        result feeds :meth:`infer_wave` in place of the host array.
        """
        with spans.span("serve.stage", spans.NEW) as sp:
            frames = np.asarray(frames)
            B = frames.shape[0]
            npad = self.batch_bucket(B) - B
            if npad:
                frames = np.concatenate(
                    [frames, np.repeat(frames[:1], npad, axis=0)])
            imgs = jax.device_put(frames)
            sp.add("h2d")
        return StagedWave(B=B, imgs=imgs, wave=sp.wave)

    def infer_wave(self, frames, plans: Sequence[RegionPlan],
                   beta: int = 0,
                   caches: Optional[Sequence[Optional[FeatureCache]]]
                   = None,
                   frame_ids: Optional[Sequence[int]] = None,
                   capture_beta: int = 0,
                   lb_override: Optional[int] = None,
                   defer: bool = False,
                   sessions: Optional[Sequence[int]] = None):
        """Serve one wave (B >= 1 frames) through the collapsed grid.

        frames: (B, H, W, 3); plans: per-sample RegionPlans — ANY
        (n_low, n_reuse) mix is servable in one executable; the wave
        runs at the length bucket of its LONGEST plan (or
        ``lb_override``, which may only pad further — the coalescing
        direction, zero resolution changes, zero accuracy question).
        caches/frame_ids: the per-client FeatureCaches of sessionful
        (reuse/capture) jobs — entries may be None for stateless jobs
        co-batched into a sessionful wave; each sample splices from and
        refreshes its OWN cache, never another's.

        The wave is padded up to the next batch bucket with copies of
        sample 0; padded rows are dropped from the decoded detections
        and never touch a cache (within one executable the result is
        bit-invariant to pad content — pinned by tests).

        ``frames`` may be a :class:`StagedWave` (pre-padded device
        array from :meth:`stage_frames`) and ``defer=True`` returns a
        :class:`PendingWave` instead of decoded detections — together
        the continuous scheduler's async-overlap path.

        ``sessions``: the caller's session id of each row; with
        ``frame_ids`` they are the wave's offload ids on its spans (-1
        where not given).
        """
        staged = frames if isinstance(frames, StagedWave) else None
        with spans.span("serve.infer_wave",
                        staged.wave if staged and staged.wave
                        else spans.NEW) as sp:
            if sp.wave is not None:
                none = [-1] * len(plans)
                sp.wave.offloads = tuple(zip(
                    none if sessions is None else sessions,
                    none if frame_ids is None else frame_ids))
            pending = self._dispatch(frames, plans, beta, caches,
                                     frame_ids, capture_beta, lb_override,
                                     sp)
        return pending if defer else pending.wait()

    def _dispatch(self, frames, plans: Sequence[RegionPlan], beta: int,
                  caches, frame_ids, capture_beta: int,
                  lb_override: Optional[int], sp) -> PendingWave:
        """:meth:`infer_wave` up to the executable's launch and the cache
        refresh; ``sp`` is the wave's ``serve.infer_wave`` span."""
        staged: Optional[StagedWave] = None
        if isinstance(frames, StagedWave):
            staged = frames
            B = staged.B
        else:
            frames = np.asarray(frames)
            B = frames.shape[0]
        assert len(plans) == B and B >= 1
        if caches is not None:
            assert len(caches) == B
        for i, p in enumerate(plans):
            assert p.n_reuse == 0 or (caches is not None
                                      and caches[i] is not None
                                      and beta >= 1), \
                "REUSE regions need feature caches and a restoration point"
        if caches is not None:
            # epoch guard: no splice ever reads tiles from a dead
            # replica — a REUSE plan whose cache predates the last
            # restart is refused outright (the client must invalidate
            # and bootstrap FULL)
            for i, p in enumerate(plans):
                c = caches[i]
                if p.n_reuse > 0 and c is not None \
                        and getattr(c, "epoch", 0) != self.epoch:
                    self.stats.stale_epoch_rejects += 1
                    raise StaleCacheEpoch(
                        f"sample {i}: REUSE plan carries cache epoch "
                        f"{getattr(c, 'epoch', 0)} but the replica is at "
                        f"epoch {self.epoch}")
            self.stats.reuse_splices += sum(
                1 for i, p in enumerate(plans)
                if p.n_reuse > 0 and caches[i] is not None)
        full_res = all(p.n_low == 0 and p.n_reuse == 0 for p in plans)

        Bp = self.batch_bucket(B)
        npad = Bp - B

        def pad_rows(a: np.ndarray) -> np.ndarray:
            if npad == 0:
                return a
            return np.concatenate([a, np.repeat(a[:1], npad, axis=0)])

        if staged is not None:
            assert staged.imgs.shape[0] == Bp, \
                f"staged wave padded to {staged.imgs.shape[0]} rows " \
                f"but the B bucket is {Bp}"
            imgs = staged.imgs
        else:
            imgs = jnp.asarray(pad_rows(frames))
            sp.add("h2d")
        layouts: Optional[List[pt.PlanLayout]] = None
        if full_res and lb_override is None:
            store_cap = capture_beta if caches is not None else 0
            exec_cap = self._full_cap(store_cap)
            fn = self._get_fn(0, 0, exec_cap, Bp)
            with spans.span("serve.launch") as launch:
                out = fn(self.params, imgs)
                launch.add("launches")
        else:
            # beta == 0 with a mixed plan is the paper's restore-at-
            # input case (full-length compute, upsampled input) — it has
            # no restoration point, so reuse plans are barred (per-plan
            # assert above) and tiles are never captured
            beta_eff = beta if not full_res else max(beta, 1)
            nws = [pt.plan_n_windows(p, self.part) for p in plans]
            lb = (self.length_bucket(max(nws)) if lb_override is None
                  else lb_override)
            assert lb >= max(nws) and lb in self.length_edges, \
                f"lb_override {lb} cannot hold {max(nws)} windows " \
                f"(edges {self.length_edges})"
            with spans.span("serve.layout") as lay:
                layouts = [pt.plan_layout(p.states, lb, self.part)
                           for p in plans]
                arrays, wave_key = pt.stack_plan_layouts(layouts)
                # key slots of the real rows' pre-restoration global
                # attention, and the pad keys among them (the flash
                # kernel skips their blocks)
                w2 = self.part.window ** 2
                lay.add("keys", lb * w2 * B)
                lay.add("pad_keys", sum(lb - n for n in nws) * w2)
            tiles_in = self._wave_tiles(layouts, caches, npad)
            # mixed execs always capture at their restoration point;
            # beta_eff == 0 has none, so it never captures
            exec_cap = beta_eff
            store_cap = beta_eff if caches is not None else 0
            fn = self._get_fn(lb, beta_eff, exec_cap, Bp)
            with spans.span("serve.args") as put:
                args = [jnp.asarray(pad_rows(arrays[k]))
                        for k in _LAYOUT_ARGS]
                put.add("h2d", len(args))
            kw = {} if self.jit else {"ids_key": wave_key}
            with spans.span("serve.launch") as launch:
                out = fn(self.params, imgs, *args, tiles_in, **kw)
                launch.add("launches")

        if exec_cap:
            (boxes, scores, classes), tiles_out = out
            if store_cap and caches is not None:
                self._refresh_caches(caches, tiles_out, layouts,
                                     store_cap,
                                     frame_ids if frame_ids is not None
                                     else [-1] * B)
        else:
            boxes, scores, classes = out
        self.stats.offloads += B
        return PendingWave(boxes, scores, classes, B, self.score_thresh,
                           sp.wave)

    def _zeros_tiles(self, Bp: int) -> jnp.ndarray:
        """Cached all-zero reuse-tiles input for reuse-free waves (a
        device-side fill — no h2d traffic, allocated once per B)."""
        z = self._zero_tiles.get(Bp)
        if z is None:
            part = self.part
            z = jnp.zeros((Bp, part.n_regions,
                           part.windows_per_full_region,
                           part.tokens_low_region, self.cfg.d_model),
                          self.act_dtype)
            self._zero_tiles[Bp] = z
        return z

    def _wave_tiles(self, layouts: List[pt.PlanLayout], caches,
                    npad: int) -> jnp.ndarray:
        """(Bp, n_regions, d^2, w^2, D) stacked per-sample reuse tiles.

        Rows are (n_regions,)-padded: entries past a sample's n_reuse
        gather clipped garbage that the restoration scatter routes to
        the sentinel.  Device-resident caches stack on device — zero
        h2d tile bytes; host caches are uploaded (and accounted) here.
        """
        with spans.span("serve.tiles") as sp:
            B = len(layouts)
            if caches is None or all(l.n_reuse == 0 for l in layouts):
                if B + npad not in self._zero_tiles:
                    sp.add("jit_launches", 2)      # zeros: convert, broadcast
                return self._zeros_tiles(B + npad)
            part = self.part
            tile = (part.n_regions, part.windows_per_full_region,
                    part.tokens_low_region, self.cfg.d_model)
            gathered, host_bytes = [], 0
            for l, c in zip(layouts, caches):
                if l.n_reuse == 0 or c is None or c.tiles is None:
                    gathered.append(None)
                    continue
                ids = np.where(l.reuse_ids < part.n_regions, l.reuse_ids, 0)
                g = c.gather(ids)
                if isinstance(g, np.ndarray):
                    # only the real rows are payload; the clipped pad rows
                    # are an artifact of the padded gather
                    host_bytes += g[:l.n_reuse].nbytes
                else:
                    sp.add("h2d")                  # the ids
                    sp.add("jit_launches")         # gather_tiles
                gathered.append(g)
            if host_bytes == 0:
                rows = [g if g is not None
                        else jnp.zeros(tile, self.act_dtype)
                        for g in gathered]
                rows += [rows[0]] * npad
                # each zero row is two computations; jnp.stack expands
                # every row and concatenates them
                sp.add("jit_launches", 2 * sum(g is None for g in gathered)
                       + len(rows) + 1)
                return jnp.stack(rows)
            self.stats.tile_bytes_h2d += host_bytes
            rows = [np.asarray(g) if g is not None
                    else np.zeros(tile, np.dtype(self.act_dtype))
                    for g in gathered]
            rows += [rows[0]] * npad
            sp.add("h2d")
            return jnp.asarray(np.stack(rows))

    def _refresh_caches(self, caches, tiles_out, layouts, cap: int,
                        frame_ids) -> None:
        """Refresh each real sessionful sample's cache with its captured
        tiles.  Padded rows and cache-less samples are never written."""
        with spans.span("serve.cache_refresh") as sp:
            B = len(caches)
            reuse_rows = [l.reuse_ids[:l.n_reuse] if l is not None
                          else np.zeros((0,), np.int32)
                          for l in (layouts or [None] * B)]
            if self.device_cache:
                for i, c in enumerate(caches[:B]):
                    if c is None:
                        continue
                    tiles = mr.take_sample_tiles(tiles_out, np.int32(i))
                    # the take, and the refresh where it overwrites in place
                    sp.add("jit_launches", 1 + c.refreshes_in_place(tiles))
                    c.update(tiles, reuse_rows[i], cap, frame_ids[i],
                             epoch=self.epoch)
            else:
                tiles_np = np.asarray(tiles_out)
                sp.add("d2h")
                live = [i for i, c in enumerate(caches[:B]) if c is not None]
                self.stats.tile_bytes_d2h += sum(tiles_np[i].nbytes
                                                 for i in live)
                for i in live:
                    caches[i].update(tiles_np[i], reuse_rows[i], cap,
                                     frame_ids[i], epoch=self.epoch)

    # ------------------------------------------------------------------
    # speculative REUSE execution (the spliced forward starts before the
    # payload lands; serve/scheduler.py owns admission and resolution)

    def infer_speculative(self, pred_canvas: np.ndarray, plan: RegionPlan,
                          beta: int, cache: FeatureCache,
                          frame_idx: int) -> Tuple[List[Dict],
                                                   FeatureCache]:
        """Launch a plan's spliced forward on a PREDICTED canvas.

        The canvas substitutes the in-flight LOW/FULL regions' pixels
        with the session's prediction source (:func:`predict_canvas`);
        REUSE regions splice from the cache exactly as the real forward
        would.  Same plan, same length bucket, B=1 — the warmed
        ``(lb, beta, beta, 1)`` executable, so speculation adds ZERO
        grid keys.  Capture goes into a :meth:`FeatureCache.
        speculative_clone`, never the live session: a discarded
        speculation leaves the real cache byte-identical, and the epoch
        guard applies to the clone exactly as to a real splice.
        Returns ``(dets, clone)``; the scheduler patches or discards on
        payload arrival and commits the clone only on success.
        """
        clone = cache.speculative_clone()
        dets = self.infer_wave(pred_canvas[None], [plan], beta,
                               caches=[clone], frame_ids=[frame_idx])
        return dets[0], clone

    # ------------------------------------------------------------------
    # N=1 conveniences (thin wrappers over infer_wave)

    def infer(self, frame: np.ndarray, mask: Optional[np.ndarray] = None,
              beta: int = 0) -> List[Dict]:
        plan = (RegionPlan.from_mask(mask) if mask is not None
                else RegionPlan(np.zeros((self.part.n_regions,), np.int8)))
        return self.infer_wave(frame[None], [plan], beta)[0]

    def infer_plan(self, frame: np.ndarray, plan: RegionPlan,
                   beta: int = 0, cache: Optional[FeatureCache] = None,
                   frame_idx: int = -1,
                   capture_beta: int = 0) -> List[Dict]:
        """Stateful three-state inference for one client frame.

        Splices the cached feature tiles of the plan's REUSE regions in
        at the restoration point, and (when ``cache`` is given) refreshes
        the cache with this forward's restoration-point tiles — captured
        at ``beta`` for mixed forwards, at ``capture_beta`` for full-res
        ones — so the NEXT offload can reuse them.
        """
        return self.infer_wave(
            frame[None], [plan], beta,
            caches=None if cache is None else [cache],
            frame_ids=[frame_idx], capture_beta=capture_beta)[0]


# ---------------------------------------------------------------------------
# speculative-prediction helpers (host-side numpy; the scheduler drives
# them around ServerModel.infer_speculative)


def predict_canvas(part: Partition, region_px: int,
                   pred_frame: np.ndarray,
                   plan: RegionPlan) -> np.ndarray:
    """The speculative forward's input: the session's prediction source
    standing in for the in-flight LOW/FULL regions, REUSE regions filled
    0.5 gray exactly as the codec fills them in a real decoded canvas
    (their pixels never reach the splice — bit-faithful anyway)."""
    canvas = np.asarray(pred_frame, np.float32).copy()
    nRw = part.regions_w
    for j in np.nonzero(np.asarray(plan.states) == REUSE)[0]:
        ry, rx = divmod(int(j), nRw)
        canvas[ry * region_px:(ry + 1) * region_px,
               rx * region_px:(rx + 1) * region_px] = 0.5
    return canvas


def region_divergence(part: Partition, region_px: int,
                      decoded: np.ndarray, predicted: np.ndarray,
                      plan: RegionPlan) -> np.ndarray:
    """(n_regions,) mean |decoded - predicted| per TRANSMITTED region
    (REUSE rows stay 0 — nothing was predicted there).  The patch pass
    recomputes only regions whose real decoded content diverged from
    the speculative prediction beyond the tolerance."""
    div = np.zeros((part.n_regions,), np.float32)
    states = np.asarray(plan.states).reshape(-1)
    nRw = part.regions_w
    for j in np.nonzero(states != REUSE)[0]:
        ry, rx = divmod(int(j), nRw)
        sl = (slice(ry * region_px, (ry + 1) * region_px),
              slice(rx * region_px, (rx + 1) * region_px))
        div[j] = float(np.abs(np.asarray(decoded, np.float32)[sl]
                              - predicted[sl]).mean())
    return div


def build_patch_plan(plan: RegionPlan,
                     diverged: np.ndarray) -> RegionPlan:
    """The cheap patch pass's plan: transmitted regions that CONVERGED
    (prediction within tolerance) flip to REUSE — splicing the
    speculative forward's captured tiles — and only diverged regions
    stay LOW/FULL.  Window count can only shrink, so the patch runs at
    an equal-or-smaller length bucket of the existing grid (zero new
    executable keys).  Callers handle the all-converged case (no patch
    compute at all) before building a plan, so at least one transmitted
    window always remains."""
    states = np.asarray(plan.states).copy()
    diverged = np.asarray(diverged, bool).reshape(-1)
    assert diverged.any(), "all-converged speculations need no patch"
    states[(states != REUSE) & ~diverged] = REUSE
    return RegionPlan(states.astype(np.int8))


# ---------------------------------------------------------------------------
# policies


class Policy:
    """Decides the offload configuration for each frame to be offloaded.

    Returns dict(mask (n_regions,), quality, beta, use_tracker: bool).
    Temporal-reuse policies additionally return a three-state ``plan``
    (partition.RegionPlan, bucket-exact in n_reuse) and may return
    ``capture_beta`` (the restoration point full-res offloads capture
    feature tiles at); they must set ``reuse_k`` (the staleness bound K)
    so the Simulation provisions a per-client FeatureCache.
    """
    name = "policy"
    use_tracker = True
    reuse_k = 0                 # K > 0 enables the per-client FeatureCache

    def decide(self, sim: "Simulation", frame_idx: int) -> Dict:
        raise NotImplementedError

    def observe_completion(self, e2e_latency: float) -> None:
        pass


@dataclass
class SimResult:
    policy: str
    video: str
    trace: str
    rendering_f1: List[float] = field(default_factory=list)
    inference_f1: List[float] = field(default_factory=list)
    e2e_latency: List[float] = field(default_factory=list)
    offload_interval: List[int] = field(default_factory=list)
    delay_parts: List[Dict] = field(default_factory=list)
    overhead: Dict[str, List[float]] = field(default_factory=dict)
    sizes: List[float] = field(default_factory=list)

    def summary(self) -> Dict:
        def med(x):
            return float(np.median(x)) if len(x) else float("nan")
        return {
            "policy": self.policy, "video": self.video, "trace": self.trace,
            "median_rendering_f1": med(self.rendering_f1),
            "mean_rendering_f1": (float(np.mean(self.rendering_f1))
                                  if self.rendering_f1 else float("nan")),
            "mean_inference_f1": (float(np.mean(self.inference_f1))
                                  if self.inference_f1 else float("nan")),
            "median_e2e_latency": med(self.e2e_latency),
            "median_interval": med(self.offload_interval),
            "median_net_delay": med([d["net"] for d in self.delay_parts]),
            "median_inf_delay": med([d["inf"] for d in self.delay_parts]),
            "median_codec_delay": med([d["enc"] + d["dec"]
                                       for d in self.delay_parts]),
            "median_queue_delay": med([d.get("queue", 0.0)
                                       for d in self.delay_parts]),
        }


class Simulation:
    """One (video, trace, policy) run."""

    def __init__(self, frames: np.ndarray, gt_dets: List[List[Dict]],
                 trace, policy: Policy, server: ServerModel,
                 part: Partition, patch_px: int, fps: int = 10,
                 delay_model: Optional[CodecDelayModel] = None,
                 inf_delay=None,
                 faults: Optional[FaultInjector] = None,
                 robust: Optional[RobustConfig] = None):
        self.frames = frames
        self.gt_dets = gt_dets            # full-res model outputs per frame
        self.trace = trace
        self.policy = policy
        self.server = server
        self.part = part
        self.fps = fps
        self.dt = 1.0 / fps
        self.codec = MixedResCodec(part, patch_px, part.downsample)
        self.delay_model = delay_model or CodecDelayModel()
        self.inf_delay = inf_delay        # InferenceDelayModel
        self.analyzer = mo.RegionMotionAnalyzer(part, patch_px)
        self.tracker = LKTracker()
        self.net_est = ThroughputEstimator()
        self.state = SystemState()
        # temporal-reuse session state: one FeatureCache per client
        # stream, provisioned only for reuse-capable policies (reuse_k =
        # the staleness bound K)
        self.feature_cache: Optional[FeatureCache] = (
            FeatureCache(part.n_regions, max_age=policy.reuse_k)
            if policy.reuse_k > 0 else None)

        # failure model: fault schedule + the deadline/retry/backoff
        # state machine (both optional — None keeps the legacy
        # fault-free, deadline-free lifecycle byte-identical)
        self.faults = faults
        self.robust = robust
        self.ladder = (DegradationLadder(robust) if robust is not None
                       else None)
        self.rstats = fresh_rstats()
        self.offload_seq = 0
        # the N=1 scheduling plane: immediate dedicated execution with
        # the shared stale-epoch NACK + crash-restart semantics
        # (serve/scheduler.py — the multi-client engine swaps in a
        # WaveScheduler over the same per-frame step methods)
        self.scheduler = SoloScheduler(self)

        # runtime state
        self.cache_dets: List[Dict] = []
        self.cache_frame = -1
        self.tracker_frame = -1           # frame the tracker state is at
        self.inflight: Optional[Dict] = None
        self.last_offload_frame = -10 ** 9
        self.m = np.zeros((part.n_regions,), np.float32)
        self.m_f = 0.0

    # ------------------------------------------------------------------
    # per-frame steps.  Single-client ``run`` below and the multi-client
    # engine (serve/edge.py) drive the SAME methods; the engine replaces
    # the synchronous server call in _start_offload with batched waves.

    def rho(self) -> np.ndarray:
        return mo.region_density(self.tracker.boxes(), self.part,
                                 self.analyzer.patch_px)

    def _motion_tick(self, frame_idx: int, res: SimResult) -> None:
        t0 = time.perf_counter()
        self.m, self.m_f = self.analyzer.update(self.frames[frame_idx])
        res.overhead.setdefault("motion_wall", []).append(
            time.perf_counter() - t0)

    def _should_offload(self, frame_idx: int) -> bool:
        """Back-to-back: a new offload starts as soon as none is in
        flight (frame 0 is skipped — the motion model needs a delta).
        After a failure, the ladder's exponential backoff additionally
        holds the retry until ``retry_at`` — while it holds (and at shed
        level), rendering rides the LK tracker."""
        if self.inflight is not None or frame_idx <= 0:
            return False
        if self.ladder is not None \
                and frame_idx * self.dt < self.ladder.retry_at:
            return False
        return True

    def _note_offload_gap(self, frame_idx: int, res: SimResult) -> None:
        if self.last_offload_frame >= 0:
            # the first offload has no predecessor: recording its warm-up
            # gap as an inter-offload interval would bias the median
            res.offload_interval.append(frame_idx - self.last_offload_frame)
        self.state.eta = frame_idx - max(self.last_offload_frame, 0)
        self.state.kappa = self.tracker.retention

    def _inf_delay_s(self, beta: int, n_d: int, n_r: int) -> float:
        """Inference-delay estimate; tolerates legacy 2-arg models."""
        if self.inf_delay is None:
            return 0.05
        try:
            return self.inf_delay(beta, n_d, n_r)
        except TypeError:
            return self.inf_delay(beta, n_d)

    def _prepare_offload(self, frame_idx: int, now: float,
                         res: SimResult) -> Dict:
        """Device side of an offload: policy decision, codec encode, and
        the device-computable Eq. (2) delay terms.  Marks the client busy
        (``inflight``) but does NOT run server inference — the caller
        finishes the job via :meth:`_finish_offload` (immediately for the
        single-client path, at wave time for the batched edge)."""
        decision = self.policy.decide(self, frame_idx)
        if self.ladder is not None:
            # retries after failures go out degraded: FULL regions
            # promoted to LOW (lowest motion first), quality dropped
            decision = self.ladder.degrade(decision, self.m)
        quality = decision["quality"]
        beta = decision["beta"]
        plan: Optional[RegionPlan] = decision.get("plan")
        if plan is None:
            plan = RegionPlan.from_mask(decision["mask"])
        mask = plan.low_mask()
        n_r = plan.n_reuse
        reuse_mask = plan.reuse_mask() if n_r > 0 else None

        frame = self.frames[frame_idx]
        if decision.get("blank") is not None:       # RoI masking baselines
            frame = frame.copy()
            rpx = self.part.region * self.analyzer.patch_px
            nRw = self.part.regions_w
            for j in np.nonzero(decision["blank"])[0]:
                ry, rx = divmod(int(j), nRw)
                frame[ry * rpx:(ry + 1) * rpx, rx * rpx:(rx + 1) * rpx] = 0.5
        t0 = time.perf_counter()
        enc, decoded = self.codec.encode(frame, mask, quality,
                                         reuse_mask=reuse_mask)
        res.overhead.setdefault("codec_wall", []).append(
            time.perf_counter() - t0)
        size = enc.payload_bytes * SIZE_SCALE
        n_d = int(mask.sum())
        beta_eff = beta if (n_d > 0 or n_r > 0) else 0

        tput, rtt = self.trace.at(now)
        if self.faults is not None:
            tput, rtt = self.faults.net(now, tput, rtt)
        job = {
            "frame": frame_idx, "submit": now, "decoded": decoded,
            "mask": mask, "n_d": n_d, "beta": beta_eff,
            "plan": plan, "n_r": n_r,
            "capture_beta": decision.get("capture_beta", 0),
            "tput": tput, "rtt": rtt, "size": size,
            "t_enc": self.delay_model.encode_delay(self.part, n_d, quality,
                                                   n_reuse=n_r),
            "t_up": size * 8.0 / tput,
            "t_dec": self.delay_model.decode_delay(self.part, n_d,
                                                   n_reuse=n_r),
            "t_inf": self._inf_delay_s(beta_eff, n_d, n_r),
            "done_at": float("inf"), "dets": None,
            "seq": self.offload_seq,
            # plan-header metadata (ships ahead of the payload; the
            # continuous scheduler's speculative-REUSE admission reads
            # it before the LOW/FULL windows land): the REUSE +
            # predicted-still-LOW fraction of the plan, and the motion
            # analyzer's confidence that the previous decoded frame
            # predicts the in-flight regions
            "spec_frac": (plan.n_reuse
                          + int(((plan.states == LOW)
                                 & (self.m * self.m_f < 1e-3)).sum()))
            / self.part.n_regions,
            "spec_conf": mo.prediction_confidence(self.m, plan.states,
                                                  m_f=self.m_f),
            # SLO-derived deadline: past it the client abandons the
            # offload and the LK tracker covers the gap
            "deadline": (now + self.robust.slo_s
                         if self.robust is not None else float("inf")),
        }
        self.offload_seq += 1
        if decision.get("degraded"):
            job["degraded"] = decision["degraded"]
            job["demoted"] = decision.get("demoted")
            self.rstats["degraded_offloads"] += 1
        self.inflight = job
        self.last_offload_frame = frame_idx
        return job

    def _finish_offload(self, job: Dict, dets: List[Dict],
                        queue_delay: float = 0.0,
                        t_dec: Optional[float] = None,
                        t_inf: Optional[float] = None) -> None:
        """Server side of an offload: attach detections and finalise the
        Eq. (2) end-to-end latency.  ``queue_delay`` (and wave-amortised
        ``t_dec``/``t_inf`` overrides) come from the edge scheduler.
        The fault schedule hooks in here: edge stalls stretch the
        service time, and a dropped response (or one arriving at a
        crashed replica) marks the job LOST — its result never comes
        back, only the client-side deadline reaps it."""
        t_dec = job["t_dec"] if t_dec is None else t_dec
        t_inf = job["t_inf"] if t_inf is None else t_inf
        arrival = job["submit"] + job["t_enc"] + job["t_up"]
        if self.faults is not None:
            t_inf = t_inf + self.faults.stall_extra(arrival + queue_delay)
        e2e = (job["t_enc"] + job["t_up"] + queue_delay + t_dec + t_inf
               + job["rtt"])
        job["dets"] = dets
        job["inf_f1"] = det.frame_f1(dets, self.gt_dets[job["frame"]])
        job["e2e"] = e2e
        job["done_at"] = job["submit"] + e2e
        job["parts"] = {"enc": job["t_enc"], "net": job["t_up"] + job["rtt"],
                        "dec": t_dec, "inf": t_inf, "queue": queue_delay}
        if self.faults is not None:
            if self.faults.response_dropped(job["seq"]) \
                    or self.faults.edge_down(arrival):
                job["lost"] = True
                job["done_at"] = float("inf")
            elif self.faults.response_duplicated(job["seq"]):
                job["dup"] = True

    def _start_offload(self, frame_idx: int, now: float, res: SimResult):
        """Single-client path: prepare, then hand to the scheduling
        plane (immediate dedicated inference for N=1)."""
        job = self._prepare_offload(frame_idx, now, res)
        self.scheduler.submit(job, now)

    def _complete_offload(self, res: SimResult, now_frame: int) -> Dict:
        fl = self.inflight
        self.inflight = None
        if fl.get("stale_epoch"):
            # the edge refused the splice (tiles from a dead replica):
            # drop the dead cache and bootstrap FULL next offload — no
            # backoff, the edge is healthy, just a new generation
            self.rstats["stale_epoch_nacks"] += 1
            if self.feature_cache is not None:
                self.feature_cache.invalidate()
            return fl
        if fl.get("rejected"):
            # edge admission shed: REJECTED response — track locally,
            # retry degraded after backoff
            self.rstats["rejected"] += 1
            if self.ladder is not None:
                self.ladder.on_failure(fl["done_at"])
                self.rstats["max_ladder_level"] = max(
                    self.rstats["max_ladder_level"], self.ladder.level)
            return fl
        if fl["frame"] <= self.cache_frame:
            # stale response: older than the rendered head — discarded,
            # never rendered
            self.rstats["stale_discards"] += 1
            return fl
        if fl.get("dup"):
            # the duplicate copy arrives later, behind the (advanced)
            # rendered head, and dies on the staleness guard above
            self.rstats["dup_discards"] += 1
        res.e2e_latency.append(fl["e2e"])
        res.inference_f1.append(fl["inf_f1"])
        res.delay_parts.append(fl["parts"])
        res.sizes.append(fl["size"])
        self.net_est.observe(fl["tput"], fl["rtt"], t=fl["done_at"])
        self.policy.observe_completion(fl["e2e"])
        if self.ladder is not None:
            self.ladder.on_success()

        if self.feature_cache is not None \
                and fl.get("demoted") is not None and len(fl["demoted"]):
            # ladder-demoted regions went out LOW: their freshly captured
            # tiles are low-fidelity stopgaps, so expire them from the
            # reuse-eligible set rather than letting one degraded offload
            # poison the next K splices
            self.feature_cache.expire(fl["demoted"])
        self.cache_dets = fl["dets"]
        self.cache_frame = fl["frame"]
        if self.policy.use_tracker:
            # reinit at the offloaded frame, catch up to the present
            self.tracker.reinit(self.frames[fl["frame"]], fl["dets"])
            for fi in range(fl["frame"] + 1, now_frame):
                self.tracker.step(self.frames[fi])
            self.tracker_frame = max(now_frame - 1, fl["frame"])
        return fl

    def _poll_inflight(self, now: float, now_frame: int,
                       res: SimResult) -> Optional[Dict]:
        """Deadline-bounded completion check: deliver a response due by
        ``now`` unless its deadline passed first — a LOST job (response
        never coming) or a LATE one (arriving past the deadline, behind
        the rendered head) is abandoned and the tracker covers the gap.
        Returns the job on delivery, else None."""
        job = self.inflight
        if job is None:
            return None
        deadline = job.get("deadline", float("inf"))
        if np.isfinite(job["done_at"]) \
                and job["done_at"] <= min(now, deadline):
            return self._complete_offload(res, now_frame)
        if now >= deadline:
            self._abandon_offload(job, min(now, job["deadline"]))
        return None

    def _abandon_offload(self, job: Dict, now: float) -> None:
        """Client-side timeout: give up on the offload, climb the
        degradation ladder, and back off before retrying."""
        self.inflight = None
        job["abandoned"] = True
        if job.get("lost"):
            self.rstats["lost_responses"] += 1
        else:
            self.rstats["timeouts"] += 1
            if np.isfinite(job["done_at"]):
                # the response does arrive eventually — after the
                # deadline — and is discarded, never rendered
                self.rstats["late_discards"] += 1
        if self.ladder is not None:
            self.ladder.on_failure(now)
            self.rstats["max_ladder_level"] = max(
                self.rstats["max_ladder_level"], self.ladder.level)

    def _edge_fault_tick(self, prev: float, now: float) -> None:
        """Single-client path owns its replica: crash-restarts apply
        through the shared scheduling plane (the multi-client engine
        drives the shared replica's restarts through the same
        ``edge_restart_tick`` helper)."""
        self.scheduler.fault_tick(prev, now)

    def _render_tick(self, frame_idx: int, res: SimResult) -> None:
        # rendering for this frame: exact cache hit, else tracker
        if frame_idx == self.cache_frame or not self.policy.use_tracker:
            rendered = self.cache_dets
        else:
            t0 = time.perf_counter()
            if self.tracker_frame < frame_idx:
                self.tracker.step(self.frames[frame_idx])
                self.tracker_frame = frame_idx
            rendered = self.tracker.boxes()
            self.rstats["tracker_frames"] += 1
            res.overhead.setdefault("tracker_wall", []).append(
                time.perf_counter() - t0)
        res.rendering_f1.append(det.frame_f1(rendered,
                                             self.gt_dets[frame_idx]))

    # ------------------------------------------------------------------
    def run(self, video_name: str = "video") -> SimResult:
        res = SimResult(policy=self.policy.name, video=video_name,
                        trace=getattr(self.trace, "name", "trace"))
        n = len(self.frames)
        prev = -1.0
        for fi in range(n):
            now = fi * self.dt

            self._edge_fault_tick(prev, now)
            self._motion_tick(fi, res)
            # completions due by now (deadline-bounded)
            self._poll_inflight(now, fi, res)
            # schedule next offload (back-to-back upon completion,
            # backed off after failures)
            if self._should_offload(fi):
                self._note_offload_gap(fi, res)
                self._start_offload(fi, now, res)
            self._render_tick(fi, res)
            prev = now
        # flush the final in-flight offload: its latency / delay parts /
        # inference F1 belong in the result even though the clip ended
        # (unless its deadline already reaped it)
        self._poll_inflight(float("inf"), n, res)
        return res
