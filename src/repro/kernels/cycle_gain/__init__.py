from repro.kernels.cycle_gain.cycle_gain import cycle_gain
from repro.kernels.cycle_gain.ops import cycle_gain_padded
from repro.kernels.cycle_gain.ref import cycle_gain_ref

__all__ = [
    "cycle_gain",
    "cycle_gain_padded",
    "cycle_gain_ref",
]
