"""Share of the window in which a query is in flight and no routing or
walk call of the program is open: the query waits on the host."""
from benchlib import timeline


def read(run):
    return timeline.open_share(run, timeline.QUERY,
                               (timeline.ROUTE, timeline.WALK))
