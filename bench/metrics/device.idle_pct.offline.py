"""Share of the traced window with no operation on the device, averaged
over the cell's chips."""
from bench import readers


def read(run):
    return readers.idle_pct(run)
