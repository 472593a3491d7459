"""Backbone step: backbone FLOPs of the offloads completed in the traced
span, each at its plan's real window count (padding not counted), over
the seconds in which the device was busy in that span times the chip's
bf16 peak, in percent.  The cell offers a fixed rate, so its work per
second is fixed; a faster step shows as less busy time for the same
FLOPs.  The f32 lane runs its matmuls as one bf16 pass, so bf16 is its
peak."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
from chipbench import flops as F  # noqa: E402
from chipbench import tracing as TR  # noqa: E402


def read(ctx):
    lo, hi = ctx["lo_s"], ctx["hi_s"]
    done = [j for j in ctx["jobs"] if lo <= j.done <= hi]
    busy = TR.busy_ns(ctx["device_events"], ctx["trace_lo_ns"],
                      ctx["trace_hi_ns"]) * 1e-9
    if not done or busy <= 0:
        return None
    work = sum(F.frame_flops(ctx["model"], j.off.n_low, j.off.n_reuse,
                             ctx["beta"]) for j in done)
    peak = F.peaks(ctx["device_kind"])["bf16_flops"]
    return 100.0 * work / (busy * peak)
