"""Percent of the resident edges that the queries ran in a Pallas kernel:
the mean ``pallas_edge_share`` of the ``drone/session/query`` spans in the
window (the share of edges in partitions whose edge backend is a kernel)
x 100. A program whose spans carry no such number reads nothing."""
from bench import program_trace


def read(run):
    if run.trace is None:
        return None
    share = program_trace.stat_per(run.trace, "pallas_edge_share",
                                   "session/query")
    return None if share is None else 100.0 * share
