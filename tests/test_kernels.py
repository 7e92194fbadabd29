"""Pallas kernel allclose sweeps vs ref.py oracles (interpret mode on CPU;
TPU is the target per DESIGN.md §5)."""
import numpy as np
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st

from repro.graphgen import powerlaw_graph, random_graph
from repro.kernels import bsp_spmv, ops, ref
from repro.kernels.bsp_spmv import TM, TN
from repro.kernels.segment_combine import W, segment_combine_windowed

SEMIRINGS = ["plus_times", "min_plus"]


def _rand_tiles(rng, T, n_dst_tiles, n_src_tiles, semiring, density=0.3):
    ident = 0.0 if semiring == "plus_times" else np.inf
    tiles = np.full((T, TM, TN), ident, np.float32)
    mask = rng.random((T, TM, TN)) < density
    tiles[mask] = rng.uniform(0.1, 5.0, size=int(mask.sum())).astype(np.float32)
    # dst-major sorted, every dst tile covered
    tile_dst = np.sort(rng.integers(0, n_dst_tiles, size=T).astype(np.int32))
    tile_dst[:n_dst_tiles] = np.arange(n_dst_tiles)
    tile_dst = np.sort(tile_dst)
    tile_src = rng.integers(0, n_src_tiles, size=T).astype(np.int32)
    return tiles, tile_dst, tile_src


@pytest.mark.parametrize("semiring", SEMIRINGS)
@pytest.mark.parametrize("T,n_dst,n_src,K", [
    (4, 2, 2, 1), (9, 3, 2, 4), (16, 4, 4, 8), (5, 5, 1, 128),
])
def test_bsp_spmv_matches_ref(semiring, T, n_dst, n_src, K):
    rng = np.random.default_rng(T * 100 + K)
    tiles, td, ts = _rand_tiles(rng, T, n_dst, n_src, semiring)
    vals = rng.uniform(0, 3, size=(n_src, TN, K)).astype(np.float32)
    got = bsp_spmv(jnp.asarray(tiles), jnp.asarray(td), jnp.asarray(ts),
                   jnp.asarray(vals), n_dst_tiles=n_dst, semiring=semiring)
    want = ref.ref_tile_spmv(jnp.asarray(tiles), jnp.asarray(td),
                             jnp.asarray(ts), jnp.asarray(vals), n_dst,
                             semiring)
    got, want = np.asarray(got), np.asarray(want)
    both_inf = np.isinf(got) & np.isinf(want)
    np.testing.assert_allclose(np.where(both_inf, 0, got),
                               np.where(both_inf, 0, want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("combiner", ["sum", "min", "max"])
@pytest.mark.parametrize("E,n_rows,K,Be", [
    (100, 64, 1, 128), (1000, 300, 4, 256), (3000, 500, 8, 512),
    (50, 400, 1, 128),  # many empty windows
])
def test_segment_combine_matches_ref(combiner, E, n_rows, K, Be):
    rng = np.random.default_rng(E + K)
    dst = np.sort(rng.integers(0, n_rows, size=E).astype(np.int64))
    msgs = rng.uniform(-2, 2, size=(E, K)).astype(np.float32)
    layout = ops.window_align_edges(dst, n_rows, block_edges=Be)
    got = np.asarray(layout(jnp.asarray(msgs), combiner=combiner))[:n_rows]
    want = np.asarray(ref.ref_segment_combine(jnp.asarray(msgs),
                                              jnp.asarray(dst.astype(np.int32)),
                                              layout.n_windows * W, combiner))[:n_rows]
    both_inf = np.isinf(got) & np.isinf(want) & (np.sign(got) == np.sign(want))
    np.testing.assert_allclose(np.where(both_inf, 0, got),
                               np.where(both_inf, 0, want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("semiring", SEMIRINGS)
@pytest.mark.parametrize("kernel", ["tiles", "windowed"])
def test_spmv_end_to_end_powerlaw(semiring, kernel):
    g = powerlaw_graph(500, seed=2, weighted=True)
    rng = np.random.default_rng(0)
    vals = rng.uniform(0, 3, size=(g.n_vertices, 2)).astype(np.float32)
    ident = 0.0 if semiring == "plus_times" else np.inf
    dense = np.full((g.n_vertices, g.n_vertices), ident, np.float32)
    if semiring == "plus_times":
        np.add.at(dense, (g.dst, g.src), g.weights)
        want = dense @ vals
    else:
        np.minimum.at(dense, (g.dst, g.src), g.weights)
        want = (dense[:, :, None] + vals[None, :, :]).min(axis=1)
    got = np.asarray(ops.spmv(g.src, g.dst, g.weights, vals, g.n_vertices,
                              semiring=semiring, kernel=kernel))
    both_inf = np.isinf(got) & np.isinf(want)
    np.testing.assert_allclose(np.where(both_inf, 0, got),
                               np.where(both_inf, 0, want), rtol=2e-4, atol=2e-4)


def test_tile_layout_dense_crosscheck():
    g = random_graph(300, 900, seed=3, weighted=True)
    layout = ops.build_tiles(g.src, g.dst, g.weights, 300, 300, "min_plus")
    dense = ref.dense_from_tiles(layout.tiles, layout.tile_dst,
                                 layout.tile_src, layout.n_dst_tiles,
                                 layout.n_src_tiles, "min_plus")
    want = np.full((layout.n_dst_tiles * TM, layout.n_src_tiles * TN), np.inf,
                   np.float32)
    np.minimum.at(want, (g.dst, g.src), g.weights)
    np.testing.assert_array_equal(dense, want)


@settings(max_examples=10, deadline=None)
@given(st.integers(1, 40), st.integers(1, 3), st.integers(0, 5),
       st.sampled_from(SEMIRINGS))
def test_bsp_spmv_property(T_extra, n_tiles, seed, semiring):
    rng = np.random.default_rng(seed)
    T = n_tiles + T_extra
    tiles, td, ts = _rand_tiles(rng, T, n_tiles, n_tiles, semiring,
                                density=0.15)
    vals = rng.uniform(0, 2, size=(n_tiles, TN, 1)).astype(np.float32)
    got = bsp_spmv(jnp.asarray(tiles), jnp.asarray(td), jnp.asarray(ts),
                   jnp.asarray(vals), n_dst_tiles=n_tiles, semiring=semiring)
    want = ref.ref_tile_spmv(jnp.asarray(tiles), jnp.asarray(td),
                             jnp.asarray(ts), jnp.asarray(vals), n_tiles,
                             semiring)
    got, want = np.asarray(got), np.asarray(want)
    both_inf = np.isinf(got) & np.isinf(want)
    np.testing.assert_allclose(np.where(both_inf, 0, got),
                               np.where(both_inf, 0, want), rtol=1e-4,
                               atol=1e-4)


# --------------------------------------------------------------------------- #
# dtype support (satellite: layouts honor program.dtype; int32 min_plus for
# CC label propagation, with the wrap-safe halved pad identity)
# --------------------------------------------------------------------------- #
def test_bsp_spmv_int32_min_plus():
    from repro.kernels.ref import tile_pad_identity
    rng = np.random.default_rng(7)
    ident = int(tile_pad_identity("min_plus", np.int32))
    tiles = np.full((3, TM, TN), ident, np.int32)
    mask = rng.random((3, TM, TN)) < 0.2
    tiles[mask] = rng.integers(0, 50, size=int(mask.sum()))
    td = np.array([0, 1, 1], np.int32)
    ts = np.array([0, 0, 1], np.int32)
    vals = rng.integers(0, 1000, size=(2, TN, 2)).astype(np.int32)
    got = bsp_spmv(jnp.asarray(tiles), jnp.asarray(td), jnp.asarray(ts),
                   jnp.asarray(vals), n_dst_tiles=2, semiring="min_plus")
    assert got.dtype == jnp.int32
    want = ref.ref_tile_spmv(jnp.asarray(tiles), jnp.asarray(td),
                             jnp.asarray(ts), jnp.asarray(vals), 2,
                             "min_plus")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_segment_combine_int32_min():
    rng = np.random.default_rng(11)
    E, n_rows = 300, 200
    dst = np.sort(rng.integers(0, n_rows, size=E).astype(np.int64))
    msgs = rng.integers(-50, 50, size=(E, 3)).astype(np.int32)
    layout = ops.window_align_edges(dst, n_rows, block_edges=128)
    got = np.asarray(layout(jnp.asarray(msgs), combiner="min"))[:n_rows]
    assert got.dtype == np.int32
    want = np.asarray(ref.ref_segment_combine(
        jnp.asarray(msgs), jnp.asarray(dst.astype(np.int32)),
        layout.n_windows * W, "min"))[:n_rows]
    imax = np.iinfo(np.int32).max
    both_pad = (got == imax) & (want == np.inf)  # empty rows: int vs float id
    np.testing.assert_array_equal(np.where(both_pad, 0, got),
                                  np.where(both_pad, 0, want.astype(np.int64)
                                           .clip(max=imax).astype(np.int32)))


def test_tile_layout_honors_dtype():
    g = random_graph(200, 600, seed=9, weighted=False)
    layout = ops.build_tiles(g.src, g.dst, np.zeros(g.n_edges), 200, 200,
                             "min_plus", dtype=np.int32)
    assert layout.tiles.dtype == np.int32
    vals = np.arange(200, dtype=np.int32)[:, None]
    out = np.asarray(layout(jnp.asarray(vals)))[:200]
    assert out.dtype == np.int32
    # oracle: min label over in-neighbours
    want = np.full(200, np.iinfo(np.int32).max >> 1, np.int64)
    np.minimum.at(want, g.dst, vals[g.src, 0])
    real = want < (np.iinfo(np.int32).max >> 1)
    np.testing.assert_array_equal(out[real, 0], want[real])


def test_plus_times_rejects_int_dtype():
    with pytest.raises(ValueError, match="float"):
        bsp_spmv(jnp.zeros((1, TM, TN), jnp.int32),
                 jnp.zeros(1, jnp.int32), jnp.zeros(1, jnp.int32),
                 jnp.zeros((1, TN, 1), jnp.int32), n_dst_tiles=1,
                 semiring="plus_times")


def test_default_interpret_matches_platform():
    import jax
    assert ops.default_interpret() == (jax.default_backend() != "tpu")
