"""Percent of the resident edges whose partitions' local phase sweeps once a
superstep: the mean ``one_sweep_share`` of the ``drone/session/query``
spans in the window (the share of edges in partitions that the engine's
local bound holds to one sweep for the query's program) x 100. A program
whose spans carry no such number reads nothing."""
from bench import program_trace


def read(run):
    if run.trace is None:
        return None
    share = program_trace.stat_per(run.trace, "one_sweep_share",
                                   "session/query")
    return None if share is None else 100.0 * share
