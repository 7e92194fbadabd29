"""Mean seconds per flush (``drone/stream/flush``) of its
``drone/stream/frontier`` span: recompute_frontier over all partitions."""
from bench import program_trace


def read(run):
    if run.trace is None:
        return None
    return program_trace.mean_per(run.trace, "stream/frontier",
                                  "stream/flush")
