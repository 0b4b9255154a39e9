"""Queries completed in the window per second of it."""
from benchlib import readers


def read(run):
    return readers.qps(run)
