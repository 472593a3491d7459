"""Device: share of the traced span in which no operation ran on the
device while the program was inside one of its ``serve.*`` spans (host
work of the serving grid: staging, dispatch, waiting and decoding), in
percent.  The part of ``device_idle_frac`` that the program's host work
causes; the rest is idle time outside the program (the loop's own work,
no offload due)."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
from chipbench import program_spans as PS  # noqa: E402
from chipbench import tracing as TR  # noqa: E402


def read(ctx):
    spans = PS.in_span(ctx)
    zero = PS.zero_s(ctx)
    lo, hi = ctx["trace_lo_ns"], ctx["trace_hi_ns"]
    if not spans or zero is None or hi <= lo:
        return None
    host = TR.clip(TR.merge((s, e) for name, s, e in
                            PS.on_trace_clock(spans, zero)
                            if name.startswith(PS.HOST_PREFIX)), lo, hi)
    idle = TR.gaps(ctx["device_events"], lo, hi)
    return 100.0 * PS.overlap_ns(idle, host) / (hi - lo)
