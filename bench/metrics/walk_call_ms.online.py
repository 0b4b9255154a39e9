"""Mean walk launch, launch to ids and scores on the host, ms."""
from benchlib import timeline


def read(run):
    return timeline.mean_ms(run, timeline.WALK)
