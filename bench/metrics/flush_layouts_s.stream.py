"""Mean seconds per flush (``drone/stream/flush``) of its
``drone/stream/layouts`` span: the refresh of the patched partitions' edge
layouts (core/layouts.py), or their full rebuild."""
from bench import program_trace


def read(run):
    if run.trace is None:
        return None
    return program_trace.mean_per(run.trace, "stream/layouts",
                                  "stream/flush")
