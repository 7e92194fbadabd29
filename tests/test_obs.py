"""Program spans and device scopes (``repro.obs``): the host spans of the
stream flush and the session's query path nest as documented in a profile,
the engine's superstep phases carry their named scopes into the lowered
program (metadata only), the termination vote under ``drone_vote`` apart
from the exchange, ``SessionStats.upload_bytes`` counts what a query puts
on the device, and the query spans carry the exchanged bytes."""
import glob
import os
import re
import subprocess
import sys

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.algos import BFS, SSSP, ConnectedComponents
from repro.core import EngineConfig, partition_and_build
from repro.core.engine import (_device_subgraph, _layout_block_from,
                               make_sim_runner)
from repro.graphgen import powerlaw_graph
from repro.obs import SPAN_PREFIX
from repro.session import GraphSession

FLUSH_CHILDREN = ("stream/coalesce", "stream/patch", "stream/frontier",
                  "stream/layouts", "session/on_flush")
QUERY_CHILDREN = ("session/upload", "session/layouts", "session/warm",
                  "session/runner", "session/launch", "session/fetch")
#: the phases every superstep loop has ops in (``result`` may lower to no
#: op at all: SSSP's is the state itself)
PHASES = ("init", "apply", "sweep", "pack", "exchange")


@pytest.fixture(scope="module")
def graph():
    return powerlaw_graph(300, seed=4, weighted=True).as_undirected()


def _session(graph):
    return GraphSession.from_graph(
        graph, 4, "cdbh", cfg=EngineConfig(edge_backend="pallas_windows"))


def _adds(n_vertices, n=12, seed=3):
    rng = np.random.default_rng(seed)
    s = rng.integers(0, n_vertices, n)
    d = rng.integers(0, n_vertices, n)
    w = np.full(n, 2.0, np.float32)
    return np.r_[s, d], np.r_[d, s], np.r_[w, w]


def _program_spans(tdir):
    """(start_ns, end_ns, name, upload_bytes) of every ``drone/`` event of
    the profile in ``tdir``, in start order."""
    path = glob.glob(os.path.join(tdir, "**", "*.xplane.pb"),
                     recursive=True)[0]
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(SPAN_PREFIX):
                    stats = dict(e.stats)
                    out.append((e.start_ns, e.start_ns + e.duration_ns,
                                e.name[len(SPAN_PREFIX):],
                                stats.get("upload_bytes")))
    return sorted(out)


def _inside(child, parent):
    return parent[0] <= child[0] and child[1] <= parent[1]


def test_spans_nest_under_flush_and_query(graph, tmp_path):
    sess = _session(graph)
    sess.query(ConnectedComponents())             # compiles, builds warm
    sess.query(BFS(), {"source": 0}, warm=False)
    with jax.profiler.trace(str(tmp_path)):
        sess.update(adds=_adds(graph.n_vertices))
        sess.flush()
        sess.query(ConnectedComponents())          # warm, after the flush
        sess.query(BFS(), {"source": 1}, warm=False)
    spans = _program_spans(str(tmp_path))
    names = {s[2] for s in spans}
    for name in ("stream/update", "stream/flush", "session/query") + \
            FLUSH_CHILDREN + QUERY_CHILDREN:
        assert name in names, name

    (flush,) = [s for s in spans if s[2] == "stream/flush"]
    for s in spans:
        if s[2] in FLUSH_CHILDREN:
            assert _inside(s, flush), s
    cc, bfs = [s for s in spans if s[2] == "session/query"]
    for s in spans:
        if s[2] in QUERY_CHILDREN:
            assert _inside(s, cc) or _inside(s, bfs), s
    # the graph is re-uploaded by the first query after the flush only
    uploads = [s for s in spans if s[2] == "session/upload"]
    assert len(uploads) == 1 and _inside(uploads[0], cc)
    assert cc[3] > 0 and bfs[3] == 0


def test_query_batch_span_has_the_query_children(graph, tmp_path):
    sess = _session(graph)
    sess.query_batch(BFS(), [{"source": 0}, {"source": 2}])
    with jax.profiler.trace(str(tmp_path)):
        sess.query_batch(BFS(), [{"source": 0}, {"source": 2}])
    spans = _program_spans(str(tmp_path))
    (batch,) = [s for s in spans if s[2] == "session/query_batch"]
    kids = {s[2] for s in spans if _inside(s, batch) and s is not batch}
    assert {"session/layouts", "session/warm", "session/runner",
            "session/launch", "session/fetch"} <= kids
    assert batch[3] > 0         # the lanes' warm blocks went up


# --------------------------------------------------------------------------- #
# named scopes on the superstep phases
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("edge_backend", ["coo", "pallas_windows"])
def test_sim_runner_carries_phase_scopes(graph, edge_backend):
    pg = partition_and_build(graph, 4)
    prog = SSSP()
    cfg = EngineConfig(edge_backend=edge_backend)
    args = (_device_subgraph(pg),)
    if edge_backend != "coo":
        args += (_layout_block_from(pg.ensure_edge_layouts(), pg, prog,
                                    edge_backend),)
    args += ({"source": np.int32(0)},)
    lowered = jax.jit(make_sim_runner(prog, cfg, pg.n_slots)).lower(*args)
    text = lowered.as_text(debug_info=True)
    for phase in PHASES:
        assert f"drone_{phase}" in text, phase
    # the scopes are op_name metadata and nothing else
    assert "drone_" not in lowered.as_text()


SHARD_SCRIPT = r"""
import os
import re
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax
import numpy as np
from repro.algos import SSSP
from repro.compat import make_mesh
from repro.core import EngineConfig, partition_and_build
from repro.core.engine import (_device_subgraph, make_bsp_runner,
                               shard_placement)
from repro.graphgen import powerlaw_graph

g = powerlaw_graph(300, seed=4, weighted=True).as_undirected()
pg = partition_and_build(g, 4)
mesh = make_mesh((4,), ("sub",))
cfg = EngineConfig(subgraph_axes=("sub",), backend="shard_map")
params = {"source": np.int32(0)}
go = make_bsp_runner(SSSP(), mesh, cfg, pg.n_slots)
sgs = _device_subgraph(pg, shard_placement(mesh, cfg))
with mesh:
    lowered = jax.jit(go).lower(sgs, params)
text = lowered.as_text(debug_info=True)
missing = [p for p in ("init", "apply", "sweep", "pack", "exchange")
           if "drone_" + p not in text]
assert not missing, missing
assert "drone_" not in lowered.as_text()
print("SHARD_SCOPES_OK")
"""


def test_shard_map_runner_carries_phase_scopes():
    res = subprocess.run([sys.executable, "-c", SHARD_SCRIPT],
                         capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, res.stderr[-4000:]
    assert "SHARD_SCOPES_OK" in res.stdout


# --------------------------------------------------------------------------- #
# SessionStats.upload_bytes
# --------------------------------------------------------------------------- #
def _nbytes(tree):
    return sum(int(x.nbytes) for x in jax.tree.leaves(tree))


def test_upload_bytes_counts_what_went_up(graph):
    sess = _session(graph)
    assert sess.stats.upload_bytes == 0
    sess.query(SSSP(), {"source": 0}, warm=False)
    lay = sess.pg.edge_layouts
    graph_b = _nbytes(sess._device)
    lay_b = _nbytes(lay.device_windows())
    pg = sess.pg
    warm_b = pg.n_parts * pg.v_max * 4          # one f32 identity block
    assert sess.stats.upload_bytes == graph_b + lay_b + warm_b
    assert lay.uploaded_bytes == lay_b

    # nothing new on the device: a repeat, another source, a warm start
    before = sess.stats.upload_bytes
    sess.query(SSSP(), {"source": 3}, warm=False)
    assert sess.stats.upload_bytes == before
    sess.query(SSSP(), {"source": 3})
    assert sess.stats.upload_bytes == before + warm_b   # its warm block

    # after a flush: the graph, the refreshed layout and the warm block
    sess.update(adds=_adds(graph.n_vertices))
    sess.flush()
    before = sess.stats.upload_bytes
    sess.query(SSSP(), {"source": 3})
    pg = sess.pg
    assert sess.stats.upload_bytes - before == (
        _nbytes(sess._device) + _nbytes(sess.pg.edge_layouts.device_windows())
        + pg.n_parts * pg.v_max * 4)


# --------------------------------------------------------------------------- #
# exchange_bytes on the query spans; the termination vote's own scope
# --------------------------------------------------------------------------- #
def _span_stat(tdir, name, stat):
    """``stat`` of every ``drone/<name>`` event of the profile in ``tdir``."""
    path = glob.glob(os.path.join(tdir, "**", "*.xplane.pb"),
                     recursive=True)[0]
    return [dict(e.stats).get(stat)
            for plane in ProfileData.from_file(path).planes
            for line in plane.lines for e in line.events
            if e.name == SPAN_PREFIX + name]


def test_query_spans_carry_exchange_bytes(graph, tmp_path):
    sess = _session(graph)
    sess.query(SSSP(), {"source": 0}, warm=False)
    sess.query_batch(BFS(), [{"source": 0}, {"source": 2}])
    with jax.profiler.trace(str(tmp_path)):
        _, st = sess.query(SSSP(), {"source": 1}, warm=False)
        lanes = sess.query_batch(BFS(), [{"source": 0}, {"source": 2}])
    assert st.total_bytes > 0
    assert _span_stat(str(tmp_path), "session/query", "exchange_bytes") \
        == [st.total_bytes]
    assert _span_stat(str(tmp_path), "session/query_batch",
                      "exchange_bytes") == \
        [sum(s.total_bytes for _, s in lanes)]


SHARD_EXCHANGE_SCRIPT = r"""
import glob, os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax
import numpy as np
from jax.profiler import ProfileData
from repro.algos import SSSP
from repro.compat import make_mesh
from repro.core import EngineConfig
from repro.graphgen import powerlaw_graph
from repro.session import GraphSession

g = powerlaw_graph(300, seed=4, weighted=True).as_undirected()
sess = GraphSession.from_graph(g, 4, "cdbh", mesh=make_mesh((4,), ("sub",)))
cfg = EngineConfig(subgraph_axes=("sub",))
sess.query(SSSP(), {"source": 0}, warm=False, cfg=cfg)
tdir = sys.argv[1]
with jax.profiler.trace(tdir):
    _, st = sess.query(SSSP(), {"source": 1}, warm=False, cfg=cfg)
assert st.total_bytes > 0
path = glob.glob(os.path.join(tdir, "**", "*.xplane.pb"), recursive=True)[0]
got = [dict(e.stats).get("exchange_bytes")
       for p in ProfileData.from_file(path).planes for l in p.lines
       for e in l.events if e.name == "drone/session/query"]
assert got == [st.total_bytes], (got, st.total_bytes)
print("SHARD_EXCHANGE_BYTES_OK")
"""


def test_shard_map_query_span_carries_exchange_bytes(tmp_path):
    res = subprocess.run([sys.executable, "-c", SHARD_EXCHANGE_SCRIPT,
                          str(tmp_path)],
                         capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, res.stderr[-4000:]
    assert "SHARD_EXCHANGE_BYTES_OK" in res.stdout


#: the vote's operations: the counts and, on shard_map, their all-reduce
VOTE_OPS = {"reduce_sum", "psum"}


def _scope_ops(text, scope):
    """Primitives whose innermost ``drone_`` scope in the lowered text's
    locations is ``drone_<scope>``."""
    out = set()
    for path in re.findall(r'loc\("([^"]*drone_[^"]*)"', text):
        inner = path.rsplit("drone_", 1)[1]
        if inner.split("/", 1)[0] == scope:
            out.add(inner.rsplit("/", 1)[-1])
    return out


def test_sim_runner_scopes_the_vote_apart(graph):
    pg = partition_and_build(graph, 4)
    lowered = jax.jit(make_sim_runner(SSSP(), EngineConfig(),
                                      pg.n_slots)).lower(
        _device_subgraph(pg), {"source": np.int32(0)})
    text = lowered.as_text(debug_info=True)
    assert "reduce_sum" in _scope_ops(text, "vote")
    assert not _scope_ops(text, "exchange") & VOTE_OPS
    assert _scope_ops(text, "exchange")        # the combine stays there


SHARD_VOTE_SCRIPT = r"""
import os, re, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path.insert(0, os.environ["TESTS_DIR"])
import jax
import numpy as np
from repro.algos import SSSP
from repro.compat import make_mesh
from repro.core import EngineConfig, partition_and_build
from repro.core.engine import (_device_subgraph, make_bsp_runner,
                               shard_placement)
from repro.graphgen import powerlaw_graph
from test_obs import VOTE_OPS, _scope_ops

g = powerlaw_graph(300, seed=4, weighted=True).as_undirected()
pg = partition_and_build(g, 4)
mesh = make_mesh((4,), ("sub",))
cfg = EngineConfig(subgraph_axes=("sub",), backend="shard_map")
params = {"source": np.int32(0)}
go = make_bsp_runner(SSSP(), mesh, cfg, pg.n_slots)
sgs = _device_subgraph(pg, shard_placement(mesh, cfg))
with mesh:
    text = jax.jit(go).lower(sgs, params).as_text(debug_info=True)
vote, exchange = _scope_ops(text, "vote"), _scope_ops(text, "exchange")
assert VOTE_OPS <= vote, vote
assert not exchange & VOTE_OPS, exchange
assert "pmin" in exchange, exchange
print("SHARD_VOTE_OK")
"""


def test_shard_map_runner_scopes_the_vote_apart():
    env = dict(os.environ, TESTS_DIR=os.path.dirname(__file__))
    res = subprocess.run([sys.executable, "-c", SHARD_VOTE_SCRIPT],
                         capture_output=True, text=True, timeout=600,
                         env=env)
    assert res.returncode == 0, res.stderr[-4000:]
    assert "SHARD_VOTE_OK" in res.stdout
