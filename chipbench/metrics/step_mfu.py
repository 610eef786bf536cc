"""Whole-step share of the chip's peak, in %: the stack products'
operations per second over the traced window, over chips times the bf16
peak. Bounds every kernel's roofline share from above, whatever kernels
the step runs."""

from chipbench.yardstick import work


def read(view):
    inputs = view.counters.get("inputs", 0)
    if not inputs or view.window_s <= 0:
        return None
    cfg = view.cell.config
    flops = work.stack_flops(cfg["neurons"], cfg["layers"], inputs)
    return 100.0 * flops / view.window_s / (view.chips * view.peaks["flops_per_s"])
