"""Roofline share of an attention kernel from a trace: over the kernel's
calls that lie whole inside the traced span, the least time the chip
needs for them over the device time they took.

Each call's work comes from its own shape, which a TPU trace records in
the op's result type (``f32[N,H,S,Dh]``: N groups of S tokens that
attend within their group, H heads of Dh), so the work and the time
cover the same calls, whichever wave they belong to.  A call whose
result type does not read so is left out of both."""
from __future__ import annotations

import re
from typing import Optional, Tuple

from . import flops as F
from . import tracing as TR

_RESULT = re.compile(r"^(\w+)\[(\d+(?:,\d+)*)\]")
ITEMSIZE = {"f32": 4, "bf16": 2, "f16": 2, "s8": 1}


def call_shape(op: str) -> Optional[Tuple[int, Tuple[int, ...]]]:
    """(itemsize, (N, H, S, Dh)) of an attention call from its device op
    name, or None where its result type is not one 4-d array."""
    _, _, typ = TR.op_name(op).partition(" ")
    m = _RESULT.match(typ)
    if m is None or m.group(1) not in ITEMSIZE:
        return None
    shape = tuple(int(x) for x in m.group(2).split(","))
    return (ITEMSIZE[m.group(1)], shape) if len(shape) == 4 else None


def roofline(ctx, needle: str) -> Optional[float]:
    """Percent of the roofline reached by the kernel whose ops are named
    ``needle``..., or None when the traced span holds none of its calls."""
    pk = F.peaks(ctx["device_kind"])
    lo, hi = ctx["trace_lo_ns"], ctx["trace_hi_ns"]
    least, ns = 0.0, 0.0
    for name, s, e, _ in TR.kernel_events(ctx["device_events"], needle,
                                          lo, hi):
        shape = call_shape(name)
        if shape is None:
            continue
        itemsize, (n, h, t, dh) = shape
        least += F.roofline_seconds(
            F.attention_call(n, h, t, dh, itemsize), pk)
        ns += e - s
    if ns <= 0 or least <= 0:
        return None
    return 100.0 * least / (ns * 1e-9)
