"""Edges the engine passed per warm CC (processed_edges) over the resident
edges."""
from bench import readers


def read(run):
    return readers.edge_passes(run, ("ConnectedComponents",))
