"""Share of the walk's row-iterations that expanded a real query's beam."""
from benchlib import timeline


def read(run):
    return timeline.walk_useful_share(run)
