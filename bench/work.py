"""Least bytes an edge pass must move, from the algorithm alone.

One pass over an edge reads its two 4-byte vertex ids and gathers the
4-byte value of its source; SSSP also reads the edge's 4-byte weight. BFS
(unit weights) and connected components (zero weights) read no weight.
The count follows the edges passed, never the padded arrays, so it is the
same whichever edge backend runs. A sweep that skips edges (a sparse
frontier) or reads compressed indices could beat it: a share of the
roofline past 100% then calls for a benchmark change that recounts.
"""
from __future__ import annotations

BYTES_PER_EDGE = {"BFS": 12, "ConnectedComponents": 12, "SSSP": 16}


def edge_pass_bytes(program: str, edges_passed: int) -> int:
    return BYTES_PER_EDGE[program] * int(edges_passed)
