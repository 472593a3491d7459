"""Kernels: roofline share of the Pallas flash-attention kernel
(``kernels/flash_attention``) in the global blocks after restoration,
from the device time of its calls that lie whole in the trace (HLO
instructions named ``_flash_attention.N``) and the FLOPs and bytes of
each of those calls at its own shape."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
from chipbench import kernels as K  # noqa: E402


def read(ctx):
    return K.roofline(ctx, "_flash_attention")
