"""Per step, device time of the three flash-attention kernels on the
busiest chip.  0 where the dispatcher took the XLA path (short
sequences), which is itself the reading a cell below the crossover must
give."""

from perf import trace_reduce as tr

LAYER, UNIT, MOVES, SOURCE = "kernels", "ms", "step_ms_p50", "device_trace"


def reduce(trace, run):
    dev = tr.busiest_chip(trace)
    if dev is None or not dev["ops"]:
        return None
    total, _ = tr.kernel_time(dev["ops"],
                              "|".join(run["family"].FLASH_KERNELS))
    return tr.per_step(total, run["steps_traced"])
