"""The reader of ``one_sweep_share.traverse``: the mean share the window's
``drone/session/query`` spans carry, in percent, on a small hand-made
trace; nothing on a trace whose query spans carry no share (a program that
does not count it) or on a run without a trace."""
import os
import types

import pytest
from jax.profiler import ProfileData

from bench import harness, program_trace

DATA = os.path.join(os.path.dirname(__file__), "data")
METRIC = "one_sweep_share.traverse"


def _trace(name):
    with open(os.path.join(DATA, name)) as f:
        raw = ProfileData.text_proto_to_serialized_xspace(f.read())
    return program_trace.from_serialized(raw)


def _run(trace):
    return types.SimpleNamespace(trace=trace, queries=[], peaks=None)


def test_reads_the_mean_share_in_the_window():
    read = harness.load_reader(METRIC)
    assert read(_run(_trace("one_sweep_trace.pbtxt"))) == \
        pytest.approx(100 * (1.0 + 0.75) / 2)


@pytest.mark.parametrize("name", ["edge_share_trace.pbtxt",
                                  "exchange_trace.pbtxt",
                                  "program_trace.pbtxt",
                                  "small_trace.pbtxt"])
def test_reads_nothing_without_the_share(name):
    read = harness.load_reader(METRIC)
    assert read(_run(_trace(name))) is None
    assert read(_run(None)) is None
