"""The program's own spans and device scopes, put on the trace's clock.

The program records spans (``repro.spans``) on ``time.perf_counter_ns``
while a profiler session is active, and names its device work with
``jax.named_scope``.  The readers of the per-layer metrics that read
them find them here.  A program without the recorder (an older commit)
has no ``repro.spans``: every function then finds nothing, and the
readers return None.

**The trace's zero.**  The harness puts the zero of the device trace at
``perf_counter()`` just before ``start_trace`` and does not pass it in
``ctx``.  :func:`zero_s` derives it from what ``ctx`` holds: a wave's
dispatch time on the host clock (``waves[i][4]``) is taken just before
its ``chipbench.stage`` span opens, and that span's start on the trace's
clock is in ``host_spans``; the two are a clock read apart.  A later
``benchmark`` change replaces this module's derivation by passing the
zero in ``ctx``.
"""
from __future__ import annotations

import bisect
from typing import Dict, List, Optional, Tuple

from . import tracing as TR

HOST_PREFIX = "serve."


def recorder():
    """The program's span recorder, or None where the program has none."""
    try:
        from repro import spans
    except ImportError:
        return None
    return spans


def in_span(ctx) -> List:
    """The program's spans that lie whole in the traced span (host
    clock), oldest first; [] without a recorder."""
    rec = recorder()
    if rec is None:
        return []
    return rec.recorded(int(ctx["lo_s"] * 1e9), int(ctx["hi_s"] * 1e9))


def waves(spans) -> Dict[int, List]:
    """The spans of each wave, by wave id."""
    out: Dict[int, List] = {}
    for s in spans:
        if s.wave is not None:
            out.setdefault(s.wave.wid, []).append(s)
    return out


def zero_s(ctx) -> Optional[float]:
    """The host-clock time (s) of the device trace's zero, from each
    wave's dispatch time and its rebased ``chipbench.stage`` span; the
    latest estimate, since each lies early by one clock read."""
    stage = {st.get("wave"): s for name, s, _, st in ctx["host_spans"]
             if name == "chipbench.stage"}
    cands = [w[4] - stage[w[0]] * 1e-9 for w in ctx["waves"]
             if w[0] in stage]
    return max(cands) if cands else None


def on_trace_clock(spans, zero: float) -> List[Tuple[str, float, float]]:
    """(name, start ns, end ns) of spans on the trace's clock."""
    z = zero * 1e9
    return [(s.name, s.t0 - z, s.t1 - z) for s in spans]


def overlap_ns(a, b) -> float:
    """Length of the intersection of two sorted lists of disjoint
    intervals."""
    out, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        out += max(0.0, e - s)
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def frames_done(ctx) -> int:
    lo, hi = ctx["lo_s"], ctx["hi_s"]
    return sum(1 for j in ctx["jobs"] if lo <= j.done <= hi)


# ---------------------------------------------------------------------------
# device scopes


_MODULES: Dict[str, List[Tuple[float, float, str]]] = {}


def _module_events(path: Optional[str]) -> List[Tuple[float, float, str]]:
    """(start, end, module name) of the ``XLA Modules`` line of a device
    trace, sorted; read once per trace file."""
    if path is None:
        return []
    if path not in _MODULES:
        mods, _, _ = TR.read(path, device_line="XLA Modules")
        _MODULES[path] = sorted((s, e, name.split("(")[0])
                                for name, s, e, _ in mods)
    return _MODULES[path]


def op_scopes(device_events, modules
              ) -> List[Tuple[str, float, float]]:
    """(scope, start, end) of each device op whose HLO instruction the
    program's noted executables put in a scope.  An op's module is its
    ``hlo_module`` stat where the trace has one (CPU), else the one of
    ``modules``, sorted (start, end, module name) events, that encloses
    it (TPU).  [] without a recorder."""
    rec = recorder()
    if rec is None:
        return []
    maps = rec.scope_map()
    starts = [m[0] for m in modules]
    out = []
    for name, s, e, st in device_events:
        module = st.get("hlo_module")
        if module is None:
            i = bisect.bisect_right(starts, s) - 1
            if i < 0 or modules[i][1] < e:
                continue
            module = modules[i][2]
        scope = maps.get(module, {}).get(TR.op_name(name).split(" ")[0])
        if scope:
            out.append((scope, s, e))
    return out


def scope_ns(scoped, top: str, lo: float, hi: float) -> float:
    """Device time (union of op intervals, ns) in [lo, hi] of the ops
    under the top-level scope ``top``."""
    return sum(e - s for s, e in TR.clip(TR.merge(
        (s, e) for sc, s, e in scoped if sc.split("/")[0] == top), lo, hi))


_SCOPED: List = [None, None, []]     # (device events, trace, their scopes)


def scope_ms_per_frame(ctx, top: str) -> Optional[float]:
    """Device milliseconds under ``top`` per frame completed in the
    traced span; None where the program recorded no span there."""
    if not in_span(ctx):
        return None
    n = frames_done(ctx)
    if not n:
        return None
    from . import harness
    path = TR.latest_xplane(str(harness.TRACE_DIR))
    dev = ctx["device_events"]
    if _SCOPED[0] is not dev or _SCOPED[1] != path:
        mods = (_module_events(path)
                if any("hlo_module" not in ev[3] for ev in dev) else [])
        _SCOPED[:] = [dev, path, op_scopes(dev, mods)]
    ns = scope_ns(_SCOPED[2], top, ctx["trace_lo_ns"], ctx["trace_hi_ns"])
    return ns * 1e-6 / n
