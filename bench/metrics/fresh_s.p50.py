"""Median seconds from an iteration's update() call to the answer of the
query that follows its flush: how stale a reader's view can be."""
from bench import readers


def read(run):
    return readers.median([q.fresh_s for q in run.queries
                           if q.fresh_s is not None])
