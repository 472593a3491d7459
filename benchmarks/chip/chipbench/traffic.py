"""The one traffic generator: mobile client sessions from a data file.

A traffic file (``traffic/<name>.json``) gives every parameter: the
session count and kinds, the plan histograms each kind draws from, the
payload table, the network traces, the batch buckets and restoration
point the replica serves them with.  From a seed the generator deals
each session a sequence of (frame, plan) offloads that depends on
nothing but the seed and the session's own earlier plans, so one seed
always offers the same work.

Every seed offers the same work in another order: session ``i`` always
takes the file's ``i``-th trace, read from its first second, and the
``i``-th capture slot, and a session's plans are dealt in blocks of
``PLAN_BLOCK``, each block one stratified draw from its histogram (one
plan from each of ``PLAN_BLOCK`` equal slices of the cumulative
counts), shuffled.  Seeds differ in the order of plans, the regions
each plan picks, and the frames.

A session is a ViTMAlis client with a camera: it captures a frame every
``frame_interval_s`` and offloads it, with one offload in flight, so a
frame captured while the previous detections are not yet back is
skipped and the client offloads at the first capture after they return.
The sessions' capture clocks are spread evenly over one interval, so
the offered rate is fixed at ``sessions / frame_interval_s`` while the
replica keeps up.  An offload
is due at the edge after the uplink of its plan's payload at that
second's trace throughput plus that second's RTT.  Without
``frame_interval_s`` a session captures as soon as its detections are
back (a closed loop with no camera clock).

Optional keys: ``frame_interval_s`` (above), ``beta`` (the restoration
point, in place of the configuration's), ``start_offsets_s``
(per-session delays before the first offload, cycled; equal delays join
as a burst) and
``rebootstrap_every`` (every n-th offload of a session is a new client's
full-res bootstrap).

``make_trace`` is the base process of ``data.network_traces.make_trace``
(AR(1) log-throughput with deep fades, per (kind, index)), copied so the
yardstick cannot move with the program.
"""
from __future__ import annotations

import json
import zlib
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Tuple

import numpy as np

FULL, LOW, REUSE = 0, 1, 2
PLAN_BLOCK = 16


def make_trace(kind: str, index: int, duration_s: int = 300
               ) -> Tuple[np.ndarray, np.ndarray]:
    """Per-second (uplink bits/s, RTT s) of a synthetic 4G/5G trace."""
    seed = zlib.crc32(f"{kind}-{index}".encode())
    rng = np.random.default_rng(seed)
    if kind == "4g":
        mean_mbps, rtt_mean, vol = rng.uniform(10.4, 36.4), 0.039, 0.25
    else:
        mean_mbps, rtt_mean, vol = rng.uniform(12.2, 135.5), 0.034, 0.35
    log_mu = np.log(mean_mbps)
    x = np.empty(duration_s)
    x[0] = log_mu
    phi = 0.92
    sigma = vol * np.sqrt(1 - phi ** 2)
    for t in range(1, duration_s):
        x[t] = log_mu + phi * (x[t - 1] - log_mu) + rng.normal(0, sigma)
    tput = np.exp(x)
    for _ in range(rng.integers(1, 5)):
        t0 = rng.integers(0, duration_s - 6)
        dur = rng.integers(2, 7)
        tput[t0:t0 + dur] *= rng.uniform(0.1, 0.3)
    rtt = np.clip(rtt_mean * (1.0 + 0.5 * (mean_mbps / tput - 1.0)),
                  0.015, 0.5)
    return tput * 1e6, rtt


def load(path: Path) -> Dict:
    return json.loads(Path(path).read_text())


@dataclass
class Offload:
    session: int
    index: int                 # the session's offload count, 0 first
    frame: int                 # index into the frame pool
    states: np.ndarray         # (n_regions,) FULL / LOW / REUSE
    payload_bytes: int

    @property
    def n_low(self) -> int:
        return int((self.states == LOW).sum())

    @property
    def n_reuse(self) -> int:
        return int((self.states == REUSE).sum())


@dataclass
class Session:
    sid: int
    kind: str                  # key into the traffic file's "plans"
    stateful: bool             # keeps a feature cache (REUSE, capture)
    tput_bps: np.ndarray
    rtt_s: np.ndarray
    rng: np.random.Generator
    traffic: Dict
    n_regions: int
    frame_pool: int
    phase_s: float = 0.0       # capture clock offset within an interval
    age: np.ndarray = field(default=None)
    offloads: List[Offload] = field(default_factory=list)

    def __post_init__(self):
        self.age = np.zeros((self.n_regions,), np.int32)
        hist = np.asarray(self.traffic["plans"][self.kind], np.int64)
        self._choices = hist[:, :2]
        self._cdf = np.cumsum(hist[:, 2]) / hist[:, 2].sum()
        self._block: List[int] = []

    def _draw(self) -> np.ndarray:
        """The next (n_low, n_reuse) of the session's current block."""
        if not self._block:
            u = (np.arange(PLAN_BLOCK) + self.rng.random(PLAN_BLOCK)) \
                / PLAN_BLOCK
            idx = np.searchsorted(self._cdf, u, side="right")
            self._block = list(self.rng.permutation(idx))
        return self._choices[self._block.pop()]

    def joins_at(self, k: int) -> bool:
        """Whether offload ``k`` opens a session: the first offload, and
        with ``rebootstrap_every`` = n every n-th after it (the client
        left and a new one joined in its place)."""
        every = self.traffic.get("rebootstrap_every", 0)
        return k == 0 or bool(every and k % every == 0)

    def _states(self, k: int) -> np.ndarray:
        nR = self.n_regions
        states = np.full((nR,), FULL, np.int8)
        if self.traffic.get("bootstrap_full", False) and self.joins_at(k):
            return states                   # full-res bootstrap
        n_low, n_reuse = self._draw()
        order = self.rng.permutation(nR)
        reuse = []
        if self.stateful:
            # REUSE only regions whose tile is within the staleness
            # bound K, and always transmit at least one region
            reuse = [r for r in order
                     if self.age[r] < self.traffic["reuse_k"]][:n_reuse]
            reuse = reuse[:nR - 1]
        rest = [r for r in order if r not in set(reuse)]
        states[reuse] = REUSE
        states[rest[:n_low]] = LOW
        return states

    def next_offload(self) -> Offload:
        k = len(self.offloads)
        states = self._states(k)
        transmitted = states != REUSE
        self.age = np.where(transmitted, 0, self.age + 1).astype(np.int32)
        off = Offload(self.sid, k, int(self.rng.integers(self.frame_pool)),
                      states, payload_bytes(self.traffic, states))
        self.offloads.append(off)
        return off

    @property
    def start_delay_s(self) -> float:
        """Seconds after the loop starts at which the session sends its
        first offload: ``start_offsets_s`` (cycled over the sessions),
        so that sessions with one offset join as a burst."""
        offs = self.traffic.get("start_offsets_s") or [0.0]
        return float(offs[self.sid % len(offs)])

    def capture_after(self, elapsed_s: float) -> float:
        """The first capture of the session's camera at or after
        ``elapsed_s`` seconds (from the loop's start)."""
        every = self.traffic.get("frame_interval_s")
        t0 = self.start_delay_s + self.phase_s
        if not every or elapsed_s <= t0:
            return max(elapsed_s, t0)
        return t0 + np.ceil((elapsed_s - t0) / every - 1e-9) * every

    def due_after(self, off: Offload, elapsed_s: float) -> float:
        """When ``off``, captured at the first capture at or after
        ``elapsed_s``, is due at the edge (seconds from the start)."""
        t = self.capture_after(elapsed_s)
        return t + self.think_s(off, t)

    def think_s(self, off: Offload, elapsed_s: float) -> float:
        """Uplink of ``off``'s payload plus the RTT, at the trace second
        ``elapsed_s`` after this session started."""
        i = int(elapsed_s) % len(self.tput_bps)
        return off.payload_bytes * 8.0 / self.tput_bps[i] + self.rtt_s[i]


def payload_bytes(traffic: Dict, states: np.ndarray) -> int:
    table = traffic["_payload_index"]
    n_low = int((states == LOW).sum())
    n_reuse = int((states == REUSE).sum())
    return table[(n_low, n_reuse)]


def sessions(traffic: Dict, n_regions: int, seed: int) -> List[Session]:
    """Deal the sessions of a traffic file for ``seed``."""
    traffic = dict(traffic)
    traffic["_payload_index"] = {(a, b): c for a, b, c
                                 in traffic["payload"]["bytes"]}
    n = traffic["sessions"]
    kinds = [traffic["kinds"][i % len(traffic["kinds"])] for i in range(n)]
    pool = [(k, i) for k, idx in traffic["traces"].items() for i in idx]
    if len(pool) < n:
        raise ValueError(f"the traffic file lists {len(pool)} traces for "
                         f"{n} sessions")
    every = traffic.get("frame_interval_s") or 0.0
    out = []
    for sid in range(n):
        tput, rtt = make_trace(*pool[sid], traffic["trace_seconds"])
        out.append(Session(
            sid=sid, kind=kinds[sid], stateful=traffic["stateful"],
            tput_bps=tput, rtt_s=rtt,
            rng=np.random.default_rng([seed, sid]), traffic=traffic,
            n_regions=n_regions, frame_pool=traffic["frame_pool"],
            phase_s=sid * every / n))
    return out


def frame_pool(traffic: Dict, img: int, seed: int) -> np.ndarray:
    """(P, img, img, 3) float32 frames in [0, 1) for ``seed``."""
    rng = np.random.default_rng([seed, 0xF4A3])
    return rng.random((traffic["frame_pool"], img, img, 3), np.float32)
