"""The collector threads' CPU time over the wall time of stack + dispatch
(batch spans' ``cpu_stack_s``), over every replica's batches stacked
inside the window: low means the collectors waited."""
from bench import spans


def read(run):
    return spans.host_cpu_pct(run)
