"""Share of the traced window with no operation on the device while its
engine held a request on the host (queue waits, stack + dispatch) and no
batch was awaiting the device (engine batch spans on the device clock)."""
from bench import spans
from bench.harness import log


def read(run):
    split = spans.idle_split(run, log)
    return None if split is None else split["host"]
