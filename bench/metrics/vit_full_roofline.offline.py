"""Share of its roofline reached by the full-width executable with the
most device time in the traced window (device trace; FLOPs and bytes
from the cell's model family; which bound applies is logged)."""
from bench import readers
from bench.harness import log


def read(run):
    return readers.full_width_roofline(run, log)
