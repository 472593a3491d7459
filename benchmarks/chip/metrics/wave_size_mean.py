"""Edge loop: mean number of real offloads in a wave, over the waves
dispatched in the traced span (a count)."""


def read(ctx):
    lo, hi = ctx["lo_s"], ctx["hi_s"]
    sizes = [n for (_, n, _, _, t, _) in ctx["waves"] if lo <= t <= hi]
    return sum(sizes) / len(sizes) if sizes else None
