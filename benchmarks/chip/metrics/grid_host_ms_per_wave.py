"""Serving grid, host side: mean host milliseconds per wave inside the
program's ``stage_frames`` and ``infer_wave`` up to the dispatch of the
deferred wave (plan layouts, stacking, transfers enqueued), from the
benchmark's spans around those calls, over the waves dispatched in the
traced span."""


def read(ctx):
    lo, hi = ctx["lo_s"], ctx["hi_s"]
    ms = [h * 1e3 for (_, _, _, h, t, _) in ctx["waves"] if lo <= t <= hi]
    return sum(ms) / len(ms) if ms else None
