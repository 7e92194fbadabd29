"""Delta commutativity: ops on distinct pairs commute, and flush chunking
is invisible.

Two layers, extending the chunk-invariance pins of test_ebv_router.py:

  1. ``DeltaBuffer`` alone — interleavings of an op stream that preserve
     per-pair relative order, under any flush chunking, leave the graph
     with the identical edge multiset (the buffer's sequential-semantics
     contract, including the add-cancelled-by-delete and delete-then-add
     state-machine paths);
  2. through a ``GraphSession`` with live warm state — the incremental
     answers of monotone programs (BFS under inserts, k-core under
     deletes) after any such schedule are bit-identical, whether the warm
     entries survived each flush or the polarity gate dropped them.
"""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

import harness
from harness import canonicalize, harness_powerlaw
from repro.algos import BFS, make_kcore
from repro.core import partition_and_build
from repro.graphgen import powerlaw_graph
from repro.session import GraphSession
from repro.stream.buffer import DeltaBuffer
from repro.stream.ingest import StreamContext


def _edge_multiset(pg):
    rows = []
    for p in range(pg.n_parts):
        m = pg.emask[p]
        gs = pg.gvid[p][pg.esrc[p][m]]
        gd = pg.gvid[p][pg.edst[p][m]]
        w = pg.ew[p][m]
        rows.append(np.stack([gs.astype(np.int64), gd.astype(np.int64),
                              w.astype(np.int64)], 1))
    rows = np.concatenate(rows)
    return rows[np.lexsort(rows.T[::-1])]


def _make_ops(g, rng, n_new=6, n_del=4, n_churn=3):
    """Per-pair op sequences with a fixed net effect: plain inserts, plain
    deletes, add-then-delete (net absent, exercising in-buffer cancel) and
    delete-then-add (the DEL_ADD path)."""
    have = {(int(s), int(d)) for s, d in zip(g.src, g.dst)}
    pairs = sorted({(min(s, d), max(s, d)) for s, d in have})
    seqs = []

    def fresh_pair():
        while True:
            u, v = int(rng.integers(0, g.n_vertices)), \
                   int(rng.integers(0, g.n_vertices))
            if u != v and (u, v) not in have and (v, u) not in have:
                have.add((u, v))
                return u, v

    for _ in range(n_new):
        u, v = fresh_pair()
        seqs.append([("add", u, v, float(rng.integers(1, 5)))])
    for i in rng.choice(len(pairs), n_del, replace=False):
        u, v = pairs[i]
        seqs.append([("del", u, v, None)])
    for j in range(n_churn):
        u, v = fresh_pair()
        seqs.append([("add", u, v, 1.0), ("del", u, v, None)])
        if j == 0 and len(pairs) > n_del:      # delete-then-re-add a live pair
            u, v = pairs[-1]
            seqs.append([("del", u, v, None), ("add", u, v, 2.0)])
    return seqs


def _interleave(seqs, rng):
    """A random merge of the per-pair sequences that preserves each pair's
    internal order — the only order that must be preserved for the net
    delta to be well defined."""
    cursors = [0] * len(seqs)
    deck = [i for i, s in enumerate(seqs) for _ in s]
    rng.shuffle(deck)
    out = []
    for i in deck:
        out.append(seqs[i][cursors[i]])
        cursors[i] += 1
    return out


def _apply(target, ops, cuts):
    """Feed ops (both undirected directions per op, atomically) into a
    DeltaBuffer or GraphSession, flushing after positions in ``cuts``."""
    for i, (kind, u, v, w) in enumerate(ops):
        if kind == "add":
            if isinstance(target, GraphSession):
                target.update(adds=([u, v], [v, u], [w, w]))
            else:
                target.add([u, v], [v, u], [w, w])
        else:
            if isinstance(target, GraphSession):
                target.update(deletes=([u, v], [v, u]))
            else:
                target.delete([u, v], [v, u])
    # cuts land between update() calls in the session layer below; for the
    # buffer layer everything coalesces into the cut-defined chunks
        if i in cuts:
            target.flush()
    target.flush()


# --------------------------------------------------------------------------- #
@settings(max_examples=3, deadline=None)
@given(st.integers(0, 10_000))
def test_buffer_order_and_chunking_invariance(seed):
    rng = np.random.default_rng(seed)
    g = canonicalize(powerlaw_graph(160, seed=1))
    seqs = _make_ops(g, rng)
    n_ops = sum(len(s) for s in seqs)

    ref = None
    ref_stats = None
    for trial in range(3):
        pg = partition_and_build(g, 4, "cdbh")
        ctx = StreamContext("cdbh", 4, 0, g.n_vertices,
                            np.zeros(g.n_vertices, np.int64))
        buf = DeltaBuffer(pg, ctx, max_edges=None)
        ops = _interleave(seqs, np.random.default_rng(seed + trial))
        cuts = set() if trial == 0 else \
            set(rng.choice(n_ops, rng.integers(1, 4), replace=False).tolist())
        _apply(buf, ops, cuts)
        ms = _edge_multiset(buf.pg)
        if ref is None:
            ref, ref_stats = ms, buf.stats
        else:
            np.testing.assert_array_equal(ms, ref)
    # the single-flush trial actually exercised coalescing
    assert ref_stats.ops_in == 2 * n_ops
    assert ref_stats.adds_cancelled > 0
    assert ref_stats.n_flushes >= 1


# --------------------------------------------------------------------------- #
@settings(max_examples=1 if harness.FAST else 2, deadline=None)
@given(st.integers(0, 10_000))
def test_incremental_queries_commute(seed):
    """Same net delta, different op interleavings and flush chunkings:
    the warm="auto" answers of BFS and k-core are bit-identical across all
    schedules (and match regardless of which warm entries survived)."""
    rng = np.random.default_rng(seed)
    g = harness_powerlaw(160, 4)
    seqs = _make_ops(g, rng, n_new=4, n_del=3, n_churn=2)
    n_ops = sum(len(s) for s in seqs)
    kprog, kparams = make_kcore(2)

    results = []
    for trial in range(3):
        sess = GraphSession.from_graph(g, 4, "cdbh")
        try:
            sess.query(BFS(), {"source": 0})         # seed warm entries
            sess.query(kprog, kparams)
            ops = _interleave(seqs, np.random.default_rng(seed + trial))
            cuts = set() if trial == 0 else \
                set(rng.choice(n_ops, rng.integers(1, 4),
                               replace=False).tolist())
            _apply(sess, ops, cuts)
            rb, _ = sess.query(BFS(), {"source": 0})
            rk, _ = sess.query(kprog, kparams)
            results.append((np.asarray(sess.pg.collect(rb, fill=np.inf)),
                            np.asarray(sess.pg.collect(rk, fill=0))))
        finally:
            sess.close()
    for rb, rk in results[1:]:
        np.testing.assert_array_equal(rb, results[0][0])
        np.testing.assert_array_equal(rk, results[0][1])
