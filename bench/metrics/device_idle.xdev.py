"""Share of the traced window in which no operation ran on the device,
mean over the cell's chips (1 - union of device-op intervals / window).
Not read from a trace that the profiler cut before the window's end."""

from bench import metric_math


def read(record):
    return metric_math.idle_pct(record)
