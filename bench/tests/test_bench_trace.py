"""The reduction from a profiler trace to busy time, idle gaps and spans,
on a small recorded trace with known answers."""
import os
import types

import pytest
from jax.profiler import ProfileData

from bench import readers
from bench.tracing import Trace

DATA = os.path.join(os.path.dirname(__file__), "data")


@pytest.fixture(scope="module")
def trace():
    with open(os.path.join(DATA, "small_trace.pbtxt")) as f:
        raw = ProfileData.text_proto_to_serialized_xspace(f.read())
    return Trace.from_profile(ProfileData.from_serialized_xspace(raw))


def test_spans_and_ops(trace):
    assert trace.n_chips == 2
    assert trace.named("query") == [(1000.0, 10000.0)]
    assert trace.named("result_copy") == [(10000.0, 12000.0)]
    assert trace.window == (1000.0, 12000.0)
    assert trace.window_s() == pytest.approx(11e-6)


def test_busy_is_the_union_averaged_over_chips(trace):
    # chip 0: 2000 + 1000 + 2000 ns; chip 1: 2000 ns (the nested op adds
    # nothing); the XLA Modules and Async XLA Ops lines are not operations
    assert trace.busy_s() == pytest.approx((5000 + 2000) / 2 * 1e-9)
    # clipped to [2000, 6500): chip 0 1000 + 500, chip 1 2000
    assert trace.busy_s(2000, 6500) == pytest.approx(3500 / 2 * 1e-9)


def test_top_ops_and_idle_gaps(trace):
    top = dict(trace.top_ops())
    # self time, averaged over the 2 chips: the loop keeps what its body
    # does not cover
    assert top == pytest.approx({
        "%fusion.1 fusion f32[16,2097152]": 4000 / 2 * 1e-9,
        "%scatter.2 scatter f32[4194304]": 1000 / 2 * 1e-9,
        "%while.5 while s32[]": 1000 / 2 * 1e-9,
        "%gather.3 gather f32[33554432]": 1000 / 2 * 1e-9})
    gaps = trace.idle_gaps()
    # chip 0 idles [3000, 6000) and [7000, 9000) inside bench/query, and
    # [11000, 12000) mostly inside bench/result_copy
    assert gaps == [["query", pytest.approx(3e-6)],
                    ["query", pytest.approx(2e-6)],
                    ["result_copy", pytest.approx(1e-6)]]


def test_readers_on_the_trace(trace):
    q = types.SimpleNamespace(program="SSSP", processed_edges=1000,
                              answer=object())
    run = types.SimpleNamespace(trace=trace, queries=[q],
                                peaks={"hbm_bytes_per_s": 1e12})
    assert readers.idle_share(run) == pytest.approx(
        100 * (1 - 3500 / 11000))
    # client span [1000, 12000) minus the chips' mean busy time in it
    assert readers.host_share_per_query(run) == pytest.approx(
        11000e-9 - 3500e-9)
    # 16 B an SSSP edge pass over 2 chips x 3.5 us busy at 1e12 B/s
    assert readers.sweep_roofline(run) == pytest.approx(
        100 * 16000 / (2 * 3500e-9 * 1e12))


def test_no_trace_reads_nothing():
    run = types.SimpleNamespace(trace=None, queries=[], peaks=None)
    assert readers.idle_share(run) is None
    assert readers.host_share_per_query(run) is None
    assert readers.sweep_roofline(run) is None
