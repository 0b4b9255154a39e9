"""Host self time of the coordinator's merge, ms per query merged."""
from benchlib import readers


def read(run):
    return readers.self_ms(run, "merge")
