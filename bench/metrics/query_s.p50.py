"""Median seconds from a query's call to its global answer on the host."""
from bench import readers


def read(run):
    return readers.median(readers.latencies(run))
