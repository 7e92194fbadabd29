"""Megabytes a query put on the device, mean over the window's queries:
the ``upload_bytes`` each ``drone/session/query`` span carries (what the
query added to ``SessionStats.upload_bytes``) / 1e6."""
from bench import program_trace


def read(run):
    if run.trace is None:
        return None
    b = program_trace.stat_per(run.trace, "upload_bytes", "session/query")
    return None if b is None else b / 1e6
