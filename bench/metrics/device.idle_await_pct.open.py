"""Share of the traced window with no operation on the device while a
dispatched batch of its engine was not yet device-ready (dispatch return
to ``t_ready_dev``: the input copy and runtime queueing)."""
from bench import spans


def read(run):
    split = spans.idle_split(run)
    return None if split is None else split["await"]
