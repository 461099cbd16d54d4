"""Share of the HBM roofline reached by the ``bit_count`` Pallas kernel: the
bytes its calls must move (from shapes, bench.flops) at 819 GB/s over the
device time of the operations under its jit name in the trace. Bandwidth
is the bound that applies: the kernel does a few operations per byte."""

from bench import metric_math


def read(record):
    return metric_math.kernel_roofline_pct(record, "bit_count")
