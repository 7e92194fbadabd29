"""Graph500 traversed edges per second: over the window's answered
queries, the undirected input edges of the search key's component, summed,
over the window's seconds (start to the answer of the last query)."""


def read(run):
    edges = sum(q.component_edges for q in run.queries
                if q.answer is not None and q.key is not None)
    return edges / run.window_s if edges else None
