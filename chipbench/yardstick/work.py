"""The work a RadiX-net stack product needs, counted from its shapes.

Only the true edges (32 per neuron per layer) over the real inputs are
counted, never stored blocks, pad slots, pad columns or grid steps, so a
lowering that stores fewer zeros or fuses layers raises the shares that
divide by these numbers and none of them can pass 100%.
"""

from __future__ import annotations

FAN_IN = 32
F32 = 4  # bytes of a float32 activation or weight value
I32 = 4  # bytes of an int32 column index


def stack_flops(neurons: int, layers: int, inputs: int) -> float:
    """One multiply and one add per edge per input."""
    return 2.0 * FAN_IN * neurons * layers * inputs


def stack_bytes(neurons: int, phases: int, inputs: int) -> float:
    """The least traffic of one stack product: the input panel read, the
    output panel written, and each distinct phase's weights once (a
    float32 value and an int32 index per edge)."""
    panels = 2 * neurons * inputs * F32
    weights = phases * neurons * FAN_IN * (F32 + I32)
    return float(panels + weights)


def least_seconds(flops: float, nbytes: float, peaks: dict) -> tuple[float, str]:
    """(least time, the bound that sets it) on a chip with ``peaks``."""
    t_compute = flops / peaks["flops_per_s"]
    t_memory = nbytes / peaks["hbm_bytes_per_s"]
    if t_compute >= t_memory:
        return t_compute, "compute"
    return t_memory, "memory"
