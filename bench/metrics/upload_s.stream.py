"""Mean seconds per query (``drone/session/query``) of its children that
put inputs on the device: the graph's re-upload (``session/upload``), the
layout blocks (``session/layouts``) and the warm block (``session/warm``)."""
from bench import program_trace, readers

UPLOADS = ("session/upload", "session/layouts", "session/warm")


def read(run):
    if run.trace is None:
        return None
    return readers.mean(program_trace.children_s(run.trace, "session/query",
                                                 UPLOADS))
