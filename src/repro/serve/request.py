"""Serving request/response types + per-client feature-cache sessions.

:class:`FeatureCache` is the session state behind temporal region reuse
(core.partition.RegionPlan): one cache per client stream, holding the
per-region backbone-feature tiles captured at the restoration point of
that client's previous offload, plus the bookkeeping that bounds
staleness — a region may be reused at most ``max_age`` (the K of the
README's state machine) CONSECUTIVE offloads before it must be
transmitted (FULL/LOW) again.  The vision edge (serve/edge.py,
offload/simulator.py) stores real tiles; the sequence engine
(serve/engine.py) uses the same bookkeeping tiles-free to gate and
bucket reuse spans.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import numpy as np


class StaleCacheEpoch(RuntimeError):
    """A REUSE plan tried to splice tiles captured under a cache epoch
    that died with a restarted replica.  The server refuses the splice
    (the invariant: no splice ever reads tiles from a dead replica); the
    client must invalidate its FeatureCache and bootstrap FULL again.
    """


@dataclass
class ServingStats:
    """Replica-side serving telemetry: the compile surface and tile
    traffic of the bucketed-executable hot path.

    ``warmed`` flips once :meth:`warmup` has compiled the executable
    grid; every compile after that is a steady-state stall — exactly the
    p95 spike warmup exists to remove — so tests and bench_serving treat
    ``steady_compiles > 0`` as a failure, not a perf footnote.  The tile
    byte counters account the host<->device traffic of the temporal-
    reuse FeatureCache (zero in device-resident mode).
    """
    compiles: int = 0
    steady_compiles: int = 0
    steady_compile_keys: List[Tuple] = field(default_factory=list)
    # host seconds each executable took to lower and compile, by key
    compile_s: Dict[Tuple, float] = field(default_factory=dict)
    warmed: bool = False
    warmup_wall_s: float = 0.0
    offloads: int = 0
    tile_bytes_d2h: int = 0
    tile_bytes_h2d: int = 0
    # robustness telemetry: crash-restarts of this replica, reuse
    # splices actually served, and splices REFUSED because the client's
    # tiles were captured under a pre-restart epoch (StaleCacheEpoch) —
    # bench_robustness gates on stale splices SERVED staying zero, which
    # is structural: a mismatched epoch always raises before splicing
    restarts: int = 0
    reuse_splices: int = 0
    stale_epoch_rejects: int = 0

    @property
    def tile_bytes(self) -> int:
        return self.tile_bytes_d2h + self.tile_bytes_h2d

    def tile_bytes_per_offload(self) -> float:
        return self.tile_bytes / max(self.offloads, 1)

    def note_compile(self, key: Tuple, seconds: float = 0.0) -> None:
        """Record one executable compile (``seconds`` of host time); after
        warmup it counts as a steady-state stall."""
        self.compiles += 1
        self.compile_s[key] = seconds
        if self.warmed:
            self.steady_compiles += 1
            self.steady_compile_keys.append(key)

    def finish_warmup(self, t0: float, compiles_before: int,
                      now: float) -> int:
        """Close a warmup pass: flip ``warmed``, account its wall time,
        return the number of executables it compiled."""
        self.warmed = True
        self.warmup_wall_s += now - t0
        return self.compiles - compiles_before


@dataclass
class FeatureCache:
    """Per-client cached restoration-point feature tiles + reuse ages.

    ``tiles``: (n_regions, d^2, w^2, D) window-blocked per-region tiles
    (None until the first capture, and always None for bookkeeping-only
    sessions such as the sequence engine's).  Tiles are stored in
    whatever residence the server hands them: the serving hot path keeps
    them as DEVICE (jax) arrays so reuse gathers and capture refreshes
    never cross PCIe (core.mixed_res.gather_tiles / refresh_tiles, the
    stale buffer donated on update); host numpy tiles remain supported
    as the legacy / debugging mode.  ``beta``: the restoration point the
    tiles were captured at — reuse is only valid at the SAME restoration
    point.  ``age[j]``: consecutive offloads region j has been reused;
    at ``max_age`` (K) the region is forced back to FULL/LOW.
    ``epoch``: the replica generation the tiles were captured under
    (ServerModel.epoch); a restart bumps the replica's generation, so a
    REUSE plan carrying an old epoch is refused (StaleCacheEpoch) and
    the client must :meth:`invalidate` and bootstrap FULL again.

    Speculative REUSE execution additionally keeps a **prediction
    source** per session: the last payload the edge decoded for this
    client (``pred_frame`` at ``pred_frame_idx``, captured under
    ``pred_epoch``).  A speculative forward substitutes the in-flight
    LOW/FULL regions' pixels with this frame's; :meth:`pred_ok` gates
    the substitution on the SAME staleness bound K (``max_age``, in
    offloads — the prediction buffer refreshes once per served offload,
    so offload count is its native clock) and on the epoch invariant —
    a speculative result must never render from a stale epoch.
    """
    n_regions: int
    max_age: int = 4
    beta: int = -1
    tiles: Optional[np.ndarray] = None
    age: np.ndarray = None
    frame: int = -1
    warm: bool = False
    epoch: int = 0
    # speculative-prediction source (edge-side): the last decoded canvas
    # served for this session + the replica generation that decoded it
    pred_frame: Optional[np.ndarray] = None
    pred_frame_idx: int = -1
    pred_age: int = 0
    pred_epoch: int = -1
    # False on speculative clones: the tiles buffer is SHARED with the
    # real session cache, so update() must not donate it to XLA (the
    # clone owns its buffer again after its first refresh)
    owns_tiles: bool = True

    def __post_init__(self):
        if self.age is None:
            self.age = np.zeros((self.n_regions,), np.int32)

    # ------------------------------------------------------------------
    @property
    def tiles_on_device(self) -> bool:
        return self.tiles is not None and not isinstance(self.tiles,
                                                         np.ndarray)

    def eligible(self, beta: int) -> np.ndarray:
        """(n_regions,) bool: regions whose cached tile may be reused for
        an offload restoring at ``beta`` (cache warm, same restoration
        point, staleness bound not yet hit)."""
        if not self.warm or beta < 1 or beta != self.beta:
            return np.zeros((self.n_regions,), bool)
        return self.age < self.max_age

    def gather(self, reuse_ids: np.ndarray) -> np.ndarray:
        """(n_reuse, d^2, w^2, D) tiles for the plan's reuse set, in the
        cache's residence (a device gather never touches the host)."""
        assert self.tiles is not None, "cache holds no tiles yet"
        if self.tiles_on_device:
            from repro.core import mixed_res as mr
            import jax.numpy as jnp
            return mr.gather_tiles(self.tiles,
                                   jnp.asarray(reuse_ids, jnp.int32))
        return self.tiles[np.asarray(reuse_ids, np.int64)]

    def expire(self, ids) -> None:
        """Force regions out of the reuse-eligible set (age pinned to
        ``max_age``) without dropping their tiles: used for regions the
        degradation ladder transmitted at LOW fidelity — a stopgap, not
        a durable splice source.  They re-enter reuse only after a
        genuine FULL re-transmission resets their age."""
        ids = np.asarray(ids, np.int64).reshape(-1)
        self.age[ids] = self.max_age

    def invalidate(self) -> None:
        """Drop every cached tile and the warm flag — the edge replied
        StaleCacheEpoch (or the session is otherwise dead); the next
        offload must be a FULL bootstrap."""
        self.tiles = None
        self.age = np.zeros((self.n_regions,), np.int32)
        self.beta = -1
        self.frame = -1
        self.warm = False
        self.pred_frame = None
        self.pred_frame_idx = -1
        self.pred_age = 0
        self.pred_epoch = -1

    # ------------------------------------------------------------------
    # speculative-prediction source

    def note_pred(self, frame: np.ndarray, frame_idx: int,
                  epoch: int) -> None:
        """Record a served offload's decoded canvas as the session's
        prediction source (resets the prediction-staleness clock)."""
        self.pred_frame = frame
        self.pred_frame_idx = int(frame_idx)
        self.pred_age = 0
        self.pred_epoch = int(epoch)

    def pred_ok(self, epoch: int) -> bool:
        """May the stored prediction source seed a speculative forward?

        Requires a source, the staleness bound K (``max_age`` offloads
        since the source was decoded — the same K that bounds tile
        reuse), and the epoch invariant: a source decoded by a dead
        replica generation predicts nothing about the live one."""
        return (self.pred_frame is not None
                and self.pred_age < self.max_age
                and self.pred_epoch == int(epoch))

    def speculative_clone(self) -> "FeatureCache":
        """A session clone for a speculative forward to capture into.

        Shares the tile buffer (gathers never mutate it) but does NOT
        own it — the clone's first refresh allocates instead of donating
        the shared device buffer — so a discarded speculation leaves the
        real session byte-identical.  Commit via
        :meth:`commit_speculative`.
        """
        clone = FeatureCache(self.n_regions, max_age=self.max_age,
                             beta=self.beta, tiles=self.tiles,
                             age=self.age.copy(), frame=self.frame,
                             warm=self.warm, epoch=self.epoch,
                             owns_tiles=False)
        return clone

    def commit_speculative(self, clone: "FeatureCache",
                           reuse_ids: np.ndarray, beta: int, frame: int,
                           epoch: int) -> None:
        """Adopt a resolved speculation's tiles into the real session.

        ``reuse_ids``: the regions whose content derives from reuse or
        from the (converged) prediction rather than freshly transmitted
        pixels — their age advances by ONE from this cache's own
        pre-speculation ages (the clone's intermediate refreshes during
        the speculative and patch forwards are bookkeeping noise), so
        prediction-derived regions burn the staleness budget exactly
        like spliced ones and K still forces a real re-transmission.
        """
        self.tiles = clone.tiles
        self.note(reuse_ids, beta, frame, epoch=epoch)

    # ------------------------------------------------------------------
    def note(self, reuse_ids: np.ndarray, beta: int, frame: int,
             epoch: Optional[int] = None) -> None:
        """Bookkeeping-only refresh: regions in ``reuse_ids`` were reused
        this offload (age + 1), every other region was transmitted
        (age reset to 0).  ``epoch``: the replica generation serving the
        refresh (None keeps the current one — legacy callers)."""
        ids = np.asarray(reuse_ids, np.int64).reshape(-1)
        new_age = np.zeros((self.n_regions,), np.int32)
        new_age[ids] = self.age[ids] + 1
        self.age = new_age
        self.beta = int(beta)
        self.frame = int(frame)
        self.warm = True
        if epoch is not None:
            self.epoch = int(epoch)
        if self.pred_frame is not None:
            # prediction staleness advances per served offload; a
            # subsequent note_pred (the serving path records the new
            # decoded canvas right after the refresh) resets it
            self.pred_age += 1

    def update(self, tiles, reuse_ids: np.ndarray,
               beta: int, frame: int, epoch: Optional[int] = None) -> None:
        """Full refresh after a forward that captured tiles.

        Device tiles stay on device; when the cache already holds a
        same-shaped device buffer the refresh donates the stale buffer
        (mixed_res.refresh_tiles) so steady-state reuse serving never
        grows the live set.  Host (numpy) tiles keep the legacy
        host-resident behaviour.
        """
        if isinstance(tiles, np.ndarray):
            self.tiles = tiles
        else:
            if (self.owns_tiles and self.tiles_on_device
                    and self.tiles.shape == tiles.shape
                    and self.tiles.dtype == tiles.dtype):
                from repro.core import mixed_res as mr
                self.tiles = mr.refresh_tiles(self.tiles, tiles)
            else:
                # a speculative clone's first refresh: the stale buffer
                # is shared with the real session, so allocate instead
                # of donating it — the clone owns this one
                self.tiles = tiles
        self.owns_tiles = True
        self.note(reuse_ids, beta, frame, epoch=epoch)


@dataclass
class Request:
    rid: int
    prompt: np.ndarray                  # (T,) int32 token ids
    max_new_tokens: int = 32
    eos_id: Optional[int] = None
    # paper technique: spans of the prompt that may be pooled at prefill
    low_span_mask: Optional[np.ndarray] = None
    beta: int = 0
    arrival_time: float = 0.0
    # temporal reuse: the client's session identity and the spans it
    # claims unchanged since its previous request (serve/engine.py gates
    # them against the per-client FeatureCache staleness bound)
    client_id: int = -1
    reuse_span_mask: Optional[np.ndarray] = None

    def _spans(self, mask: Optional[np.ndarray],
               n: Optional[int]) -> np.ndarray:
        if mask is None or self.beta <= 0:
            return np.zeros((0,), np.int32)
        sel = np.nonzero(np.asarray(mask).reshape(-1) != 0)[0]
        if n is not None:
            sel = sel[:n]
        return sel.astype(np.int32)

    def low_spans(self, n_low: Optional[int] = None) -> np.ndarray:
        """Span indices actually pooled, in selection order.

        ``n_low``: static bucket — extra selections beyond it are dropped
        (the same trimming rule as seq_mixed_res.build_seq_pack), so the
        returned ids are the pack's identity: two requests with equal
        ``low_spans(n_low)`` produce byte-identical packs and may share a
        wave.
        """
        return self._spans(self.low_span_mask, n_low)

    def reuse_spans(self, n_reuse: Optional[int] = None) -> np.ndarray:
        """Span indices the client marked temporally reusable, with the
        same bucket-trimming rule as :meth:`low_spans`."""
        return self._spans(self.reuse_span_mask, n_reuse)

    def mask_key(self, n_low: Optional[int] = None,
                 reuse_ids: Optional[np.ndarray] = None) -> bytes:
        """Canonical wave-key bytes of the (bucket-trimmed) span layout.

        ``reuse_ids``: the EFFECTIVE reuse spans (after the engine's
        session-staleness gate) — part of the identity because co-batched
        requests must share one pack layout.
        """
        key = self.low_spans(n_low).tobytes()
        if reuse_ids is not None and len(reuse_ids):
            key += b"|" + np.asarray(reuse_ids, np.int32).tobytes()
        return key


@dataclass
class Response:
    rid: int
    tokens: List[int] = field(default_factory=list)
    prefill_done: float = 0.0
    finished: float = 0.0
    slot: int = -1

    @property
    def n_tokens(self) -> int:
        return len(self.tokens)
