"""Nearest-rank p99 of how long requests queued before the engine took
them (take minus submit, from the batch spans), over the requests of the
batches stacked inside the window."""
from bench import spans


def read(run):
    return spans.queue_ms_p99(run)
