"""Backbone step: device milliseconds of the ops under the program's
``vit.pre_beta`` scope (the patch embed, the fused prologue or pack and
the blocks before the restoration point; on a full-resolution frame,
the blocks before the capture point), in the traced span, over the
frames completed in it.  Each op is mapped to its scope through its HLO
instruction in the executable that ran it
(``repro.spans.scope_map``)."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
from chipbench import program_spans as PS  # noqa: E402


def read(ctx):
    return PS.scope_ms_per_frame(ctx, "vit.pre_beta")
