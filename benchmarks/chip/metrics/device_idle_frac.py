"""Device: share of the traced span in which no operation ran on the
device (1 - union of device op intervals / span), in percent.  At the
cell's fixed offered rate a larger share means less device time per
frame."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
from chipbench import tracing as TR  # noqa: E402


def read(ctx):
    lo, hi = ctx["trace_lo_ns"], ctx["trace_hi_ns"]
    if hi <= lo:
        return None
    busy = TR.busy_ns(ctx["device_events"], lo, hi)
    return 100.0 * (1.0 - busy / (hi - lo))
