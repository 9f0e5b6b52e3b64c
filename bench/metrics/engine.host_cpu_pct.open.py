"""The collector thread's CPU time over the wall time of stack +
dispatch (batch spans' ``cpu_stack_s``), over the batches stacked inside
the window: low means the collector waited."""
from bench import spans


def read(run):
    return spans.host_cpu_pct(run)
