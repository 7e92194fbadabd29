"""95th-percentile seconds (nearest rank) from a query's call to its
global answer on the host, over all the window's queries."""
from bench import readers


def read(run):
    return readers.nearest_rank(readers.latencies(run), 0.95)
