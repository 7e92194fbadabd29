"""Edges the engine passed per query (processed_edges) over the resident
edges: how many full sweeps a BFS or SSSP costs."""
from bench import readers


def read(run):
    return readers.edge_passes(run, ("BFS", "SSSP"))
