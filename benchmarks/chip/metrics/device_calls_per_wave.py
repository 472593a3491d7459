"""Serving grid, host side: mean number of separate host<->device calls
the program makes for one wave (executable launches, other device
computations, host->device transfers, device->host reads), counted on
the wave's spans, over the waves staged, dispatched and waited on in the
traced span."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
from chipbench import program_spans as PS  # noqa: E402


def read(ctx):
    n = [sum(s.calls for s in wave)
         for wave in PS.waves(PS.in_span(ctx)).values()
         if {"serve.infer_wave", "serve.wait"} <= {s.name for s in wave}]
    return sum(n) / len(n) if n else None
