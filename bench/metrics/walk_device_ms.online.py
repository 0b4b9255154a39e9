"""Device time of the beam-walk program per launch, from the trace."""
from benchlib import readers


def read(run):
    return readers.program_ms(run, readers.WALK_PROGRAM)
