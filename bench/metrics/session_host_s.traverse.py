"""Mean seconds per query, from the call to the global answer, in which
no chip ran an operation (the session's host work), from the trace."""
from bench import readers


def read(run):
    return readers.host_share_per_query(run)
