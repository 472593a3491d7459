"""Reduction of a profiler trace to device busy time, kernel time and
idle gaps named by the host span they fall in.

The JAX profiler writes ``<dir>/plugins/profile/<run>/*.xplane.pb``;
``jax.profiler.ProfileData`` reads it.  Device operations are the
events of the ``XLA Ops`` line of each ``/device:...`` plane, in
nanoseconds from the start of the trace.  Host spans are the
benchmark's own ``chipbench.*`` spans: the profiler's host plane where
it was recorded, or the loop's log on the host clock put on the trace's
clock (:func:`rebase`).  The arithmetic works on plain (name, start_ns,
end_ns, stats) tuples, so it is tested on a trace recorded on the CPU.
"""
from __future__ import annotations

import glob
import os
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Event = Tuple[str, float, float, Dict]

SPAN_PREFIX = "chipbench."


def latest_xplane(trace_dir: str) -> Optional[str]:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def _stats(ev) -> Dict:
    out = {}
    try:
        for k, v in ev.stats:
            out[str(k)] = v
    except Exception:                       # stats of an odd type
        pass
    return out


def read(path: str, device_line: str = "XLA Ops",
         device_plane: str = "/device:"
         ) -> Tuple[List[Event], List[Event], Dict]:
    """(device op events, host spans, layout summary) of one trace.
    Device ops are the events of the lines named ``device_line`` (a
    prefix) on the planes whose name starts with ``device_plane``."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    device: List[Event] = []
    host: List[Event] = []
    layout: Dict[str, List[str]] = {}
    for plane in pd.planes:
        lines = list(plane.lines)
        layout[plane.name] = [ln.name for ln in lines]
        if plane.name.startswith(device_plane):
            for ln in lines:
                if not ln.name.startswith(device_line):
                    continue
                for ev in ln.events:
                    device.append((ev.name, ev.start_ns, ev.end_ns,
                                   _stats(ev)))
        if plane.name.startswith("/host:"):
            for ln in lines:
                for ev in ln.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        host.append((ev.name, ev.start_ns, ev.end_ns,
                                     _stats(ev)))
    return device, host, layout


def rebase(spans, zero_s: float, lo: float, hi: float) -> List[Event]:
    """Spans logged as (name, t0, t1, stats) on the host clock, in
    seconds, as events in nanoseconds from ``zero_s``, the host-clock
    time at which the trace started; those outside [lo, hi] dropped."""
    out = []
    for name, t0, t1, st in spans:
        s, e = (t0 - zero_s) * 1e9, (t1 - zero_s) * 1e9
        if e > lo and s < hi:
            out.append((name, s, e, dict(st)))
    return out


def merge(intervals: Iterable[Tuple[float, float]]
          ) -> List[Tuple[float, float]]:
    """Union of intervals as sorted, disjoint intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: float, hi: float):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def busy_ns(device: Sequence[Event], lo: float, hi: float) -> float:
    """Length of the union of device op intervals inside [lo, hi]."""
    return sum(e - s for s, e in clip(merge((s, e) for _, s, e, _
                                            in device), lo, hi))


def gaps(device: Sequence[Event], lo: float, hi: float):
    """Idle intervals of the device inside [lo, hi]."""
    busy = clip(merge((s, e) for _, s, e, _ in device), lo, hi)
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def enclosing(host: Sequence[Event], t: float) -> str:
    """Name of the innermost host span that covers time ``t``."""
    best, best_len = "no span", float("inf")
    for name, s, e, _ in host:
        if s <= t <= e and e - s < best_len:
            best, best_len = name, e - s
    return best


def named_gaps(device, host, lo, hi, top: int = 10):
    """The ``top`` longest idle gaps as [host span, seconds]."""
    gs = sorted(gaps(device, lo, hi), key=lambda g: g[0] - g[1])[:top]
    return [[enclosing(host, (s + e) / 2), (e - s) * 1e-9] for s, e in gs]


def op_name(name: str) -> str:
    """The HLO instruction of a device op event: a TPU trace names each
    op by its whole HLO text (``%fusion.12 = f32[...] fusion(...)``), so
    keep the instruction and its result type."""
    head, _, rest = name.partition(" = ")
    return f"{head.lstrip('%')} {rest.split(' ', 1)[0]}" if rest else head


def op_key(ev: Event) -> str:
    """Group key of a device op: its instruction and result type, and
    the program it ran in where the trace records one."""
    name, _, _, st = ev
    prog = st.get("hlo_module") or st.get("program_id") or ""
    return f"{op_name(name)} [{prog}]" if prog else op_name(name)


def top_ops(device: Sequence[Event], lo: float, hi: float, top: int = 10):
    tot: Dict[str, float] = {}
    for ev in device:
        s, e = max(ev[1], lo), min(ev[2], hi)
        if e > s:
            k = op_key(ev)
            tot[k] = tot.get(k, 0.0) + (e - s)
    ranked = sorted(tot.items(), key=lambda kv: -kv[1])[:top]
    return [[k, v * 1e-9] for k, v in ranked]


def kernel_events(device: Sequence[Event], prefix: str, lo: float,
                  hi: float) -> List[Event]:
    """The ops that lie whole inside [lo, hi] and whose HLO instruction
    name starts with ``prefix`` (a Pallas kernel's call name, such as
    ``_flash_attention``)."""
    return [ev for ev in device if ev[1] >= lo and ev[2] <= hi
            and op_name(ev[0]).startswith(prefix)]
