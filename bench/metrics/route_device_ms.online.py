"""Device time of the routing program per call, from the trace."""
from benchlib import readers


def read(run):
    return readers.program_ms(run, readers.ROUTE_PROGRAM)
