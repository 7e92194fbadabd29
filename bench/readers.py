"""Arithmetic the metric readers share (``bench/metrics/<name>.py``).

Each helper takes the ``harness.Run`` and returns a number, or None where
the run holds nothing to read: no trace, no device operation in it, or no
query of the programs asked for.
"""
from __future__ import annotations

import math
import statistics

from bench.work import edge_pass_bytes


def latencies(run) -> list:
    return [q.latency_s for q in run.queries]


def median(values):
    return statistics.median(values) if values else None


def nearest_rank(values, p: float):
    """The ``p``-th percentile by nearest rank (the largest of fewer than
    ``1 / (1 - p)`` samples)."""
    if not values:
        return None
    s = sorted(values)
    return s[max(0, math.ceil(p * len(s)) - 1)]


def mean(values):
    return sum(values) / len(values) if values else None


def edge_passes(run, programs) -> float:
    """Mean edges passed per query over the resident edges."""
    got = [q.processed_edges / run.resident_edges for q in run.queries
           if q.program in programs and q.answer is not None]
    return mean(got)


def _traced(run) -> bool:
    return run.trace is not None and bool(run.trace.ops) and \
        bool(run.trace.spans)


def query_spans(run) -> list:
    """(query, start_ns, end_ns) from each query's call to its global
    answer, on the trace's clock; [] unless every query has its spans."""
    if not _traced(run):
        return []
    qs = run.trace.named("query")
    cs = run.trace.named("result_copy")
    if len(qs) != len(run.queries) or len(cs) != len(run.queries):
        return []
    return [(q, a, c[1]) for q, (a, _), c in zip(run.queries, qs, cs)]


def idle_share(run):
    """Percent of the traced window in which a chip ran no operation,
    averaged over the chips."""
    if not _traced(run):
        return None
    return 100.0 * (1.0 - run.trace.busy_s() / run.trace.window_s())


def host_share_per_query(run):
    """Mean seconds of a query's client time in which no chip was busy."""
    spans = query_spans(run)
    return mean([(b - a) * 1e-9 - run.trace.busy_s(a, b)
                 for _, a, b in spans])


def sweep_roofline(run):
    """Least bytes of the window's edge passes over the chips' busy time
    inside the queries times peak HBM bandwidth, in percent."""
    spans = query_spans(run)
    if not spans or run.peaks is None:
        return None
    nbytes = sum(edge_pass_bytes(q.program, q.processed_edges)
                 for q, _, _ in spans if q.answer is not None)
    chip_s = sum(run.trace.busy_s(a, b) for q, a, b in spans
                 if q.answer is not None) * run.trace.n_chips
    if chip_s <= 0 or nbytes <= 0:
        return None
    return 100.0 * nbytes / (chip_s * run.peaks["hbm_bytes_per_s"])
