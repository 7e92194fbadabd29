"""Mean host seconds of update() + flush() per insert batch."""
from bench import readers


def read(run):
    return readers.mean([q.flush_s for q in run.queries
                         if q.flush_s is not None])
