"""Mean wait of a shard copy in its executor's queue, enqueue to drain, ms."""
from benchlib import timeline


def read(run):
    return timeline.mean_ms(run, timeline.QUEUED)
