"""Share of the roofline the sparse stack products reach, in %.

The least time the chip could take for the traced window's stack
products (the larger of their operations over peak FLOP/s and their
least bytes over HBM bandwidth, from ``yardstick.work``), over the summed
device time of every Pallas kernel event in the window. Operations are
held to the bf16 peak, the highest the chip has, so for these float32
products the compute bound is the lowest it can be.
"""

from chipbench.yardstick import radixnet, work


def read(view):
    kernel_s = view.kernel_s()
    inputs = view.counters.get("inputs", 0)
    if kernel_s <= 0 or not inputs:
        return None
    cfg = view.cell.config
    flops = work.stack_flops(cfg["neurons"], cfg["layers"], inputs)
    nbytes = work.stack_bytes(cfg["neurons"], radixnet.num_phases(cfg["neurons"]), inputs)
    least, bound = work.least_seconds(flops, nbytes, view.peaks)
    return 100.0 * least / kernel_s, (
        f"{bound} bound: least {least:.6g} s over {kernel_s:.6g} s of kernel time "
        f"for {inputs} inputs"
    )
