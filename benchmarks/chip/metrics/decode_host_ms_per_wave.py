"""Serving grid, host side: mean host milliseconds per wave in the
program's ``serve.decode`` span (the per-row reads and decode of a
wave's detections in ``PendingWave.wait``, after ``serve.ready``), over
the waves completed in the traced span."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
from chipbench import program_spans as PS  # noqa: E402


def read(ctx):
    ms = [(s.t1 - s.t0) * 1e-6 for s in PS.in_span(ctx)
          if s.name == "serve.decode"]
    return sum(ms) / len(ms) if ms else None
