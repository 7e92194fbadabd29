"""Percent of the device self time inside the queries (``bench/query``
spans) spent in operations under the engine's ``drone_exchange`` scope, the
SBS boundary combine across the chips, averaged over the chips. A program
that does not scope its termination vote apart (``drone_vote``) counts the
vote under ``drone_exchange`` too; on its trace this reads nothing."""
from bench import program_trace


def read(run):
    if run.trace is None or not any(
            sc == "vote" for ops in run.trace.scoped_ops.values()
            for _, _, sc in ops):
        return None
    return program_trace.scope_share(run.trace, "exchange",
                                     run.trace.named("query"))
