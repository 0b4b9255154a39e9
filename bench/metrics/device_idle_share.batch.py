"""Share of the traced slice in which no program ran on the device."""
from benchlib import readers


def read(run):
    return readers.idle_share(run)
