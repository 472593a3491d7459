"""Serving grid, host side, from inside the program: mean host
milliseconds per wave in its ``serve.stage`` and ``serve.infer_wave``
spans (pad and transfer of the frames, plan layouts, reuse tiles, layout
transfers, the launch, the cache refresh), over the waves whose
``serve.infer_wave`` span lies in the traced span.  The inside twin of
``grid_host_ms_per_wave``."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
from chipbench import program_spans as PS  # noqa: E402


def read(ctx):
    ms = [sum(s.t1 - s.t0 for s in wave
              if s.name in ("serve.stage", "serve.infer_wave")) * 1e-6
          for wave in PS.waves(PS.in_span(ctx)).values()
          if any(s.name == "serve.infer_wave" for s in wave)]
    return sum(ms) / len(ms) if ms else None
