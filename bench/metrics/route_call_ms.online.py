"""Mean routing call, launch to the shard mask on the host, ms."""
from benchlib import timeline


def read(run):
    return timeline.mean_ms(run, timeline.ROUTE)
