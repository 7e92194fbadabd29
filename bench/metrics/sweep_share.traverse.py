"""Percent of the device self time inside the queries (``bench/query``
spans) spent in operations under the engine's ``drone_sweep`` scope: the
local edge sweeps, against apply, pack, exchange, init and result."""
from bench import program_trace


def read(run):
    if run.trace is None:
        return None
    return program_trace.scope_share(run.trace, "sweep",
                                     run.trace.named("query"))
