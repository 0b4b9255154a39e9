"""Real queries per walk launch over the launch's padded rows."""
from benchlib import readers


def read(run):
    return readers.batch_fill(run)
