"""The comparison that decides ``correct``.

What is compared is what the timed waves produced, at the timed sizes:

* ``box_rel_rms`` — for a seeded sample of served offloads (the one
  with the most transmitted windows always among them): each served
  top-k detection is matched to the reference location whose box lies
  nearest (largest coordinate gap), among every location of every
  pyramid level; the root mean square of those gaps over the root mean
  square width and height of the matched reference boxes.  The worst
  offload counts.
* ``score_rel_rms`` — for the same detections: the relative L2 gap
  between the served scores and the reference's scores at the matched
  locations.  The worst offload counts.
* ``tiles_rel_l2`` — for every session that keeps a feature cache: the
  tiles its cache holds after its last offload (the restoration-point
  features: patch embed, the blocks before beta on the packed mixed
  sequence, LOW upsampling and the REUSE splice), against the
  reference's, as relative L2.  The worst session counts.

The scores and boxes cover the blocks after beta, the pyramid, the head
and its decode.  They are matched by location and not by rank: with
random weights the scores of the top locations lie so close together
that rounding reorders them far past the top-k.  Gaps are read as
shares of the reference's own scale, since the size of the boxes that
random weights give changes from seed to seed several times over, and
the rounding gaps with it.  The reference rebuilds each session's REUSE
sources itself, from the frame in which each region was last
transmitted.
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np

from . import reference as R


def sample_jobs(jobs, n: int, seed: int, n_windows):
    """``n`` served offloads drawn from the seed, the one with the most
    transmitted windows always first."""
    jobs = [j for j in jobs if j.done > 0]
    if not jobs:
        return []
    longest = max(jobs, key=lambda j: (n_windows(j.off), -j.off.session,
                                       -j.off.index))
    rng = np.random.default_rng([seed, 0xC4EC])
    rest = [j for j in jobs if j is not longest]
    pick = [rest[i] for i in rng.permutation(len(rest))[:max(n - 1, 0)]]
    return [longest] + pick


def last_transmitted(states_seq: List[np.ndarray]) -> np.ndarray:
    """(n_offloads, n_regions): for each offload and region, the offload
    at which the region was last transmitted (FULL or LOW)."""
    out = np.zeros((len(states_seq), len(states_seq[0])), np.int64)
    last = np.zeros(len(states_seq[0]), np.int64)
    for k, st in enumerate(states_seq):
        last = np.where(st != R.REUSE, k, last)
        out[k] = last
    return out


class Reference:
    """Reference outputs for served offloads, in the reference's or the
    control's arithmetic."""

    def __init__(self, params, g: R.Geometry, beta: int, top_k: int,
                 frames, sessions, stateful: bool, control: bool = False):
        import jax.numpy as jnp
        self.jnp = jnp
        self.params, self.g, self.beta = params, g, beta
        self.top_k = top_k
        self.frames, self.stateful = frames, stateful
        self.sessions = {s.sid: s for s in sessions}
        run = R.compiled(g, beta, control)
        self.pre, self.post, self.full = run.pre, run.post, run.full
        self.rm = R.region_map(g)
        self._pre = {}
        self._last = {}

    def _fresh(self, sid, k):
        key = (sid, k)
        if key not in self._pre:
            off = self.sessions[sid].offloads[k]
            self._pre[key] = self.pre(
                self.params, self.jnp.asarray(self.frames[off.frame]),
                self.jnp.asarray(off.states.astype(np.int32)))
        return self._pre[key]

    def restored(self, sid, k):
        """Restoration-point features of offload ``k`` of a session."""
        if sid not in self._last:
            seq = [o.states for o in self.sessions[sid].offloads]
            self._last[sid] = last_transmitted(seq)
        src = self._last[sid][k]
        out = None
        for kp in np.unique(src):
            f = self._fresh(sid, int(kp))
            if out is None:
                out = f
            else:
                mask = np.isin(self.rm, np.nonzero(src == kp)[0])
                out = self.jnp.where(self.jnp.asarray(mask)[..., None],
                                     f, out)
        return out

    def detections(self, sid, k):
        """(scores (A,), boxes (A, 4)) of offload ``k`` at every
        location."""
        jnp = self.jnp
        off = self.sessions[sid].offloads[k]
        if not self.stateful:
            s, b, _ = self.full(self.params,
                                jnp.asarray(self.frames[off.frame]))
        else:
            st = jnp.asarray(off.states.astype(np.int32))
            prev = (self.restored(sid, k - 1)
                    if (off.states == R.REUSE).any() else
                    jnp.zeros((self.g.grid, self.g.grid, self.g.d_model)))
            s, b, _ = self.post(self.params, self._fresh(sid, k), prev, st)
        return (np.asarray(s, np.float64), np.asarray(b, np.float64))

    def top(self, sid, k):
        """The reference's own top-k detections of offload ``k``."""
        s, b = self.detections(sid, k)
        i = np.argsort(-s, kind="stable")[:self.top_k]
        return s[i], b[i]


def match(served_boxes, ref_boxes):
    """(index of the nearest reference box, its largest coordinate gap)
    for each served box."""
    d = np.abs(np.asarray(served_boxes, np.float64)[:, None, :]
               - ref_boxes[None, :, :]).max(-1)
    i = d.argmin(axis=1)
    return i, d[np.arange(len(i)), i]


def box_rel_rms(gap, ref_boxes) -> float:
    """RMS box gap over the RMS width and height of the matched
    reference boxes."""
    ext = np.concatenate([ref_boxes[:, 2] - ref_boxes[:, 0],
                          ref_boxes[:, 3] - ref_boxes[:, 1]])
    scale = np.sqrt(np.mean(ext ** 2))
    g = np.sqrt(np.mean(np.asarray(gap) ** 2))
    return float(g / scale) if scale > 0 else float("inf")


def detection_numbers(served_scores, served_boxes, ref_scores, ref_boxes):
    """(score_rel_rms, box_rel_rms) of one offload."""
    i, gap = match(served_boxes, ref_boxes)
    s = np.asarray(served_scores, np.float64)
    if not (np.isfinite(s).all() and np.isfinite(gap).all()):
        return float("inf"), float("inf")
    return rel_l2(s, ref_scores[i]), box_rel_rms(gap, ref_boxes[i])


def rel_l2(got, ref) -> float:
    got = np.asarray(got, np.float64)
    ref = np.asarray(ref, np.float64)
    return float(np.linalg.norm(got - ref) / np.linalg.norm(ref))


def compare(ref: Reference, served: Dict, tiles: Dict) -> Dict[str, float]:
    """Numbers of one run.  ``served``: {(sid, k): (scores, boxes)} of the
    sampled offloads; ``tiles``: {sid: (last offload, (grid, grid, D)
    tile map)} of the stateful sessions."""
    sg, bg, tl = 0.0, 0.0, 0.0
    for (sid, k), (s, b) in served.items():
        a, c = detection_numbers(s, b, *ref.detections(sid, k))
        sg, bg = max(sg, a), max(bg, c)
    out = {"score_rel_rms": sg, "box_rel_rms": bg}
    if tiles:
        for sid, (k, grid) in tiles.items():
            e = rel_l2(grid, ref.restored(sid, k))
            tl = max(tl, e if np.isfinite(e) else float("inf"))
        out["tiles_rel_l2"] = tl
    return out


def control_outputs(ctrl: Reference, served_keys, tile_keys):
    """The control in the program's place: its detections for the same
    offloads and its tile maps for the same sessions."""
    served = {}
    for sid, k in served_keys:
        served[(sid, k)] = ctrl.top(sid, k)
    tiles = {sid: (k, np.asarray(ctrl.restored(sid, k), np.float32))
             for sid, k in tile_keys.items()}
    return served, tiles
