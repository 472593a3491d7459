"""Edge loop: median wait of an offload in the replica's FIFO queue, from
the time it was due at the edge to the dispatch of its wave (host
clock), over the offloads dispatched in the traced span."""
import numpy as np


def read(ctx):
    lo, hi = ctx["lo_s"], ctx["hi_s"]
    w = [(j.dispatch - j.due) * 1e3 for j in ctx["jobs"]
         if lo <= j.dispatch <= hi]
    return float(np.median(w)) if w else None
