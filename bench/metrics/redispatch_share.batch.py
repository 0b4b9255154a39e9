"""Shard dispatches sent again per primary shard dispatch."""
from benchlib import readers


def read(run):
    return readers.redispatch_share(run)
