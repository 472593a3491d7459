"""Backbone step: device milliseconds of the ops under the program's
``vit.post_beta`` scope (the restoration with the REUSE splice, the
blocks from the restoration point on, the final norm), in the traced
span, over the frames completed in it.  Each op is mapped to its scope
through its HLO instruction in the executable that ran it
(``repro.spans.scope_map``)."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
from chipbench import program_spans as PS  # noqa: E402


def read(ctx):
    return PS.scope_ms_per_frame(ctx, "vit.post_beta")
