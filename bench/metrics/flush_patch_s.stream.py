"""Mean seconds per flush (``drone/stream/flush``) of its ``drone/stream/patch``
span: apply_delta's routing, partition rebuilds, capacity growth, row remap
and degree refresh, on the host."""
from bench import program_trace


def read(run):
    if run.trace is None:
        return None
    return program_trace.mean_per(run.trace, "stream/patch", "stream/flush")
