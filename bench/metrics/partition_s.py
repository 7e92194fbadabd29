"""Host seconds of ``GraphSession.from_graph``: the partitioner and the
subgraph build (``core/partition.py``, ``core/subgraph.py``)."""


def read(run):
    return run.partition_s
