"""In-program spans of the serving path: one process-wide flight recorder.

A span is a named interval of host time on ``time.perf_counter_ns`` (the
clock a benchmark logs its own spans on) with its own id, its parent's
id, the wave it belongs to (with that wave's offload ids, (session,
frame index) pairs) and counts as attributes.  Closed spans go to a
bounded ring in memory; nothing is written out: readers take
:func:`recorded` after the fact.

Recording is off by default, and a span site then costs one call and one
flag check and allocates nothing.  It is on in two cases:

- while a JAX profiler session is active, so a traced window records
  exactly over its trace;
- between :func:`enable` and :func:`disable`, the operator's flight
  recorder.

While on, each span also enters a ``jax.profiler.TraceAnnotation``, so a
profile taken with the host tracer shows it.  One recorder per process,
as the JAX profiler is one per process; spans nest on one stack, so one
thread serves at a time.

Counts on a span (:meth:`Span.add`) are the host<->device calls made
inside it: ``launches`` (the wave's executable), ``jit_launches`` (every
other device computation: jitted index ops, eager ops, row slices),
``h2d`` (host->device transfers) and ``d2h`` (device->host reads).
``serve.layout`` also counts work: ``keys``, the key slots of the real
rows' pre-restoration global attention (length bucket x window tokens
per row), and ``pad_keys``, the padding among them.

Device work is named by ``jax.named_scope`` inside the traced functions
(:data:`SCOPES`).  A TPU trace names each op by its HLO instruction
alone, so :func:`note_executable` keeps each compiled executable and
:func:`scope_map` reads, on demand, which scope each instruction of each
HLO module came from.
"""
from __future__ import annotations

import collections
import itertools
import re
import time
from typing import Deque, Dict, List, Optional, Tuple

import jax
from jax._src import profiler as _jax_profiler

# the active JAX profiler session lives here (``profile_session``, None
# when no trace runs); tests/test_spans.py pins this against jax
_PROFILE = _jax_profiler._profile_state

RING = 1 << 16          # ~12 spans a wave at ~13 waves/s: minutes of serving
COUNTS = ("launches", "jit_launches", "h2d", "d2h")
# the device scopes: backbone up to the restoration point, from it on,
# and the detection head with its on-device decode
PRE_BETA, POST_BETA, HEAD = "vit.pre_beta", "vit.post_beta", "det.head"
SCOPES = (PRE_BETA, POST_BETA, HEAD)


class Wave:
    """A wave's identity on its spans: id and offload ids."""
    __slots__ = ("wid", "offloads")

    def __init__(self, wid: int):
        self.wid = wid
        self.offloads: Tuple[Tuple[int, int], ...] = ()


NEW = object()          # span(..., NEW): the span opens a new wave


class Span:
    """One recorded span (while recording is on)."""
    __slots__ = ("name", "sid", "parent", "wave", "t0", "t1", "counts",
                 "_ann")

    def __init__(self, name: str, wave):
        self.name = name
        self.sid = next(_REC.ids)
        self.parent = 0
        self.wave = Wave(next(_REC.waves)) if wave is NEW else wave
        self.t0 = self.t1 = 0
        self.counts: Dict[str, int] = {}
        self._ann = None

    def __enter__(self) -> "Span":
        stack = _REC.stack
        if stack:
            top = stack[-1]
            self.parent = top.sid
            if self.wave is None:
                self.wave = top.wave
        stack.append(self)
        self._ann = jax.profiler.TraceAnnotation(self.name)
        self._ann.__enter__()
        self.t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> bool:
        self.t1 = time.perf_counter_ns()
        self._ann.__exit__(*exc)
        self._ann = None
        stack = _REC.stack
        if stack and stack[-1] is self:
            stack.pop()
        _REC.ring.append(self)
        return False

    def add(self, key: str, n: int = 1) -> None:
        self.counts[key] = self.counts.get(key, 0) + n

    @property
    def calls(self) -> int:
        """Host<->device calls counted on this span."""
        return sum(self.counts.get(k, 0) for k in COUNTS)


class _Off:
    """What a span site gets while recording is off: enters, counts and
    names no wave, records nothing."""
    __slots__ = ()
    wave = None

    def __enter__(self) -> "_Off":
        return self

    def __exit__(self, *exc) -> bool:
        return False

    def add(self, key: str, n: int = 1) -> None:
        pass


OFF = _Off()


class Recorder:
    """The process's spans, open-span stack and noted executables."""

    def __init__(self):
        self.on = False
        self.ring: Deque[Span] = collections.deque(maxlen=RING)
        self.stack: List[Span] = []
        self.ids = itertools.count(1)
        self.waves = itertools.count()
        self.executables: Dict[str, object] = {}
        self.scopes: Dict[str, Tuple[object, str, Dict[str, str]]] = {}


_REC = Recorder()


def span(name: str, wave=None):
    """A span context manager: ``with span("serve.stage", NEW) as sp``.

    ``wave``: :data:`NEW` opens a new wave, a :class:`Wave` joins one,
    None inherits the enclosing span's.  Returns :data:`OFF` while
    recording is off."""
    if not (_REC.on or _PROFILE.profile_session is not None):
        return OFF
    return Span(name, wave)


def recording() -> bool:
    return _REC.on or _PROFILE.profile_session is not None


def enable() -> None:
    """Record from now on, whether or not a profiler session runs."""
    _REC.on = True


def disable() -> None:
    _REC.on = False


def recorded(lo_ns: Optional[int] = None,
             hi_ns: Optional[int] = None) -> List[Span]:
    """The closed spans in the ring, oldest first; with bounds, those
    that start at or after ``lo_ns`` and end at or before ``hi_ns``."""
    out = list(_REC.ring)
    if lo_ns is not None:
        out = [s for s in out if s.t0 >= lo_ns]
    if hi_ns is not None:
        out = [s for s in out if s.t1 <= hi_ns]
    return out


def clear() -> None:
    _REC.ring.clear()


# ---------------------------------------------------------------------------
# device scopes


def note_executable(key: str, compiled) -> None:
    """Keep a compiled executable (``jax.stages.Compiled``) under ``key``,
    replacing what ``key`` held, so :func:`scope_map` can read its HLO."""
    _REC.executables[key] = compiled


_INSTR = re.compile(r'^\s*(?:ROOT )?%([^\s=]+) = .*?metadata=\{[^}]*?'
                    r'op_name="([^"]*)"', re.M)
_MODULE = re.compile(r"^HloModule ([^\s,]+)", re.M)
_SUB = re.compile(r"block\d+|restore")


def scope_of(op_name: str) -> str:
    """The scope of an HLO ``op_name``: its first scope of
    :data:`SCOPES` with the sub-scope below it where that is a block or
    the restoration (``vit.post_beta/block11``, ``vit.post_beta/restore``,
    ``det.head``), or ""."""
    parts = op_name.split("/")
    for i, p in enumerate(parts):
        if p in SCOPES:
            sub = parts[i + 1] if i + 2 < len(parts) else ""
            return f"{p}/{sub}" if _SUB.fullmatch(sub) else p
    return ""


def scope_map() -> Dict[str, Dict[str, str]]:
    """{HLO module name: {instruction: scope path}} over the noted
    executables, for the instructions that carry a scope.  Read from
    each executable's optimized HLO text the first time it is asked."""
    out: Dict[str, Dict[str, str]] = {}
    for key, compiled in _REC.executables.items():
        hit = _REC.scopes.get(key)
        if hit is None or hit[0] is not compiled:
            try:
                text = compiled.as_text()
            except RuntimeError:         # an executable without HLO text
                continue
            m = _MODULE.search(text)
            instr = {}
            for name, op in _INSTR.findall(text):
                sc = scope_of(op)
                if sc:
                    instr[name] = sc
            hit = (compiled, m.group(1) if m else key, instr)
            _REC.scopes[key] = hit
        out[hit[1]] = hit[2]
    return out
