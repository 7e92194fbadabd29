"""The readers of ``exchange_share.x4`` and ``exchange_mb_per_query.x4`` on
a small hand-made trace of four chips with known answers; nothing on the
traces of a program that does not scope its vote apart or count the
exchanged bytes, nor on a run without a trace."""
import os
import types

import pytest
from jax.profiler import ProfileData

from bench import harness, program_trace

DATA = os.path.join(os.path.dirname(__file__), "data")
METRICS = ("exchange_share.x4", "exchange_mb_per_query.x4")


def _trace(name):
    with open(os.path.join(DATA, name)) as f:
        raw = ProfileData.text_proto_to_serialized_xspace(f.read())
    return program_trace.from_serialized(raw)


def _run(trace):
    return types.SimpleNamespace(trace=trace, queries=[], peaks=None)


@pytest.fixture(scope="module")
def trace():
    return _trace("exchange_trace.pbtxt")


def test_the_fixture_has_four_chips_and_the_vote(trace):
    assert sorted(trace.scoped_ops) == [0, 1, 2, 3]
    assert [sc for _, _, sc in trace.scoped_ops[3]] == [
        "sweep", "exchange", "vote", "sweep", "exchange", "vote", None]


def test_exchange_share_averages_the_chips_inside_the_queries(trace):
    read = harness.load_reader("exchange_share.x4")
    # exchange (400 + 200 c) + 200 ns a chip, mean 900, of 4200 ns a chip;
    # chip 0's exchange during the warm-up lies outside the queries
    assert read(_run(trace)) == pytest.approx(100 * 900 / 4200)


def test_exchange_mb_is_the_mean_of_the_window_queries(trace):
    read = harness.load_reader("exchange_mb_per_query.x4")
    assert read(_run(trace)) == pytest.approx((4e6 + 2e6) / 2 / 1e6)


@pytest.mark.parametrize("metric", METRICS)
@pytest.mark.parametrize("name", ["program_trace.pbtxt", "small_trace.pbtxt",
                                  "edge_share_trace.pbtxt"])
def test_reads_nothing_on_a_program_without_them(metric, name):
    read = harness.load_reader(metric)
    assert read(_run(_trace(name))) is None
    assert read(_run(None)) is None
