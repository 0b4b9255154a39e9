"""95th percentile latency from due time to answer, every request of the window."""
from benchlib import readers


def read(run):
    return readers.latency_ms(run, 95)
