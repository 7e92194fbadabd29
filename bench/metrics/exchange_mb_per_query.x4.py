"""Megabytes the SBS boundary exchange moved per query, mean over the
window's queries: the ``exchange_bytes`` each ``drone/session/query`` span
carries (the query's ``ExecutionStats.total_bytes``) / 1e6. A program
whose spans carry no such number reads nothing."""
from bench import program_trace


def read(run):
    if run.trace is None:
        return None
    b = program_trace.stat_per(run.trace, "exchange_bytes", "session/query")
    return None if b is None else b / 1e6
