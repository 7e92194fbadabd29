"""The program's spans and scopes in the trace (``bench/program_trace.py``)
and the six readers of them, on a small hand-made trace with known answers;
on a trace of a program that names nothing, every one of them reads
nothing and the benchmark's own reduction is unchanged."""
import os
import types

import pytest
from jax.profiler import ProfileData

from bench import harness, program_trace
from bench.tracing import Trace

DATA = os.path.join(os.path.dirname(__file__), "data")
NEW_METRICS = ("flush_patch_s.stream", "flush_frontier_s.stream",
               "flush_layouts_s.stream", "upload_s.stream",
               "upload_mb_per_query.stream", "sweep_share.traverse")


def _raw(name):
    with open(os.path.join(DATA, name)) as f:
        return ProfileData.text_proto_to_serialized_xspace(f.read())


@pytest.fixture(scope="module")
def trace():
    return program_trace.from_serialized(_raw("program_trace.pbtxt"))


def _run(trace):
    return types.SimpleNamespace(trace=trace, queries=[], peaks=None)


def test_program_spans_in_the_window(trace):
    names = {n for _, _, n, _, _ in trace.program_spans}
    assert {"stream/flush", "stream/patch", "session/query",
            "session/upload"} <= names
    # the warm-up query before the window is kept, and read by nobody
    assert len([s for s in trace.program_spans
                if s[2] == "session/query"]) == 3
    assert len(program_trace.named(trace, "session/query")) == 2
    assert program_trace.span_s(trace, "stream/patch") == \
        pytest.approx(2000e-9)
    assert program_trace.children_s(
        trace, "session/query",
        ("session/upload", "session/layouts", "session/warm")) == \
        [pytest.approx(1700e-9), pytest.approx(100e-9)]


def test_idle_inside_a_span(trace):
    # no operation runs during the flush; the launches leave 300 + 200 ns
    assert program_trace.idle_in(trace, "stream/patch") == \
        pytest.approx(2000e-9)
    assert program_trace.idle_in(trace, "session/launch") == \
        pytest.approx(500e-9)


def test_scopes_from_the_metadata_and_the_hlo(trace):
    # tf_op of the event metadata (fusion.92, reduce.45, init, result);
    # else the HLO op_name (while.50), or the one scope of what a
    # compiler-made fusion calls (fusion.78, fusion.80); the outer loop
    # calls several scopes and gets none
    assert [sc for _, _, sc in trace.scoped_ops[0]] == [
        None, "sweep", "sweep", "sweep", "pack", "exchange",
        "init", None, "sweep", "sweep", "sweep", "exchange", "result"]


def test_scope_self_time(trace):
    a, b = trace.window
    assert program_trace.scope_self_s(trace, "sweep", a, b) == \
        pytest.approx(5800e-9)
    assert program_trace.scope_self_s(trace, None, a, b) == \
        pytest.approx(8400e-9)
    # the loops keep only what their bodies leave them (200 + 500 ns)
    assert program_trace.scope_self_s(trace, None, a, b) - sum(
        program_trace.scope_self_s(trace, sc, a, b)
        for sc in ("init", "sweep", "pack", "exchange", "result")) == \
        pytest.approx(700e-9)
    # clipped to a stretch that cuts the first loop's sweep in half
    assert program_trace.scope_self_s(trace, "sweep", 108900, 112000) == \
        pytest.approx(900e-9)
    # on the whole window, the self time top_ops gives each operation
    assert sum(t for _, t in trace.top_ops()) == pytest.approx(8400e-9)


@pytest.mark.parametrize("metric,want", [
    ("flush_patch_s.stream", 2000e-9),
    ("flush_frontier_s.stream", 500e-9),
    ("flush_layouts_s.stream", 1500e-9),
    ("upload_s.stream", (1700e-9 + 100e-9) / 2),
    ("upload_mb_per_query.stream", (3e6 + 1e6) / 2 / 1e6),
    ("sweep_share.traverse", 100 * 5800 / 8400)])
def test_readers_on_the_trace(trace, metric, want):
    assert harness.load_reader(metric)(_run(trace)) == pytest.approx(want)


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_readers_read_nothing_without_the_program_names(metric):
    """A program without the spans and scopes (the trace of
    test_bench_trace.py), or a run without a trace."""
    bare = program_trace.from_serialized(_raw("small_trace.pbtxt"))
    read = harness.load_reader(metric)
    assert read(_run(bare)) is None
    assert read(_run(None)) is None


def test_the_benchmark_reduction_is_unchanged():
    raw = _raw("small_trace.pbtxt")
    plain = Trace.from_profile(ProfileData.from_serialized_xspace(raw))
    ext = program_trace.from_serialized(raw)
    assert ext.spans == plain.spans and ext.ops == plain.ops
    assert ext.top_ops() == plain.top_ops()
    assert ext.idle_gaps() == plain.idle_gaps()
    assert ext.program_spans == [] and not program_trace.has_scopes(ext)


def test_trace_from_file_keeps_the_program_names(tmp_path):
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_raw("program_trace.pbtxt"))
    tr = Trace.from_file(str(path))
    assert len(program_trace.named(tr, "session/query")) == 2
    assert program_trace.has_scopes(tr)
