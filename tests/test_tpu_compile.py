"""Ahead-of-time compiles for a described TPU v5e: the two Pallas kernels of
the query path and a whole simulator runner, lowered and compiled by the
TPU compiler without a chip attached.

Interpret mode (every other kernel test) never sees Mosaic's layout rules,
so a kernel can pass all of them and still be refused on the chip — the
windowed kernel's old ``(1, Be)`` block over a ``(B, Be)`` array was.
These compiles catch that class here. Nothing runs, so they say nothing
about results or speed.

The topology is described inside a module fixture (never at import time:
only one process may load the TPU library, and pytest-xdist workers each
import every test file), which skips where no v5e can be described.
"""
import importlib
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.algos import SSSP, ConnectedComponents
from repro.core import EngineConfig, make_sim_runner
from repro.core.engine import normalize_edge_backend
from repro.graphgen import kronecker_graph
from repro.kernels.bsp_spmv import TM, TN, bsp_spmv
from repro.kernels.segment_combine import segment_combine_windowed
from repro.serving.runner_cache import canonical_params
from repro.session import GraphSession

BE = 512
# the package re-exports the kernel functions under the module names
KERNEL_MODULES = [importlib.import_module(f"repro.kernels.{m}")
                  for m in ("bsp_spmv", "segment_combine")]


@pytest.fixture(scope="module")
def one_chip():
    """A described v5e chip, compiled for in compiled (non-interpret)
    Pallas mode, with the persistent compilation cache off: a compile for
    an absent chip is written to the cache but cannot be read back."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    with pytest.MonkeyPatch.context() as mp:
        # the kernels pick interpret mode from jax.default_backend(), which
        # is the CPU here: steer them to what they run as on the chip
        for mod in KERNEL_MODULES:
            mp.setattr(mod, "default_interpret", lambda: False)
        jax.clear_caches()          # no interpret-mode trace is reused
        yield SingleDeviceSharding(topo.devices[0])
        jax.clear_caches()
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _spec(x, sharding):
    return jax.ShapeDtypeStruct(np.shape(x), x.dtype, sharding=sharding)


def _has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("K", [1, 8])
@pytest.mark.parametrize("semiring,dtype", [
    ("plus_times", jnp.float32), ("min_plus", jnp.float32),
    ("min_plus", jnp.int32)])
def test_bsp_spmv_compiles(one_chip, semiring, dtype, K):
    T, n_dst, n_src = 64, 16, 16
    args = (jax.ShapeDtypeStruct((T, TM, TN), dtype, sharding=one_chip),
            jax.ShapeDtypeStruct((T,), jnp.int32, sharding=one_chip),
            jax.ShapeDtypeStruct((T,), jnp.int32, sharding=one_chip),
            jax.ShapeDtypeStruct((n_src, TN, K), dtype, sharding=one_chip))
    compiled = jax.jit(lambda t, d, s, v: bsp_spmv(
        t, d, s, v, n_dst_tiles=n_dst, semiring=semiring)
    ).lower(*args).compile()
    assert _has_kernel(compiled)


@pytest.mark.parametrize("K", [1, 8])
@pytest.mark.parametrize("combiner,dtype", [
    ("sum", jnp.float32), ("min", jnp.float32), ("min", jnp.int32)])
def test_segment_combine_compiles(one_chip, combiner, dtype, K):
    B, n_windows = 64, 16
    args = (jax.ShapeDtypeStruct((B * BE, K), dtype, sharding=one_chip),
            jax.ShapeDtypeStruct((B * BE,), jnp.int32, sharding=one_chip),
            jax.ShapeDtypeStruct((B,), jnp.int32, sharding=one_chip))
    compiled = jax.jit(lambda m, ld, bw: segment_combine_windowed(
        m, ld, bw, n_windows=n_windows, combiner=combiner)
    ).lower(*args).compile()
    assert _has_kernel(compiled)


def _sim_runner(sharding, program, params, edge_backend):
    """The whole served simulator runner — superstep loop, SBS exchange,
    kernel sweeps — as ``GraphSession`` builds it, over a Kronecker graph,
    compiled."""
    g = kronecker_graph(10, seed=0, weighted=True)
    sess = GraphSession.from_graph(g, n_parts=4, partitioner="cdbh")
    eb, cfg = normalize_edge_backend(
        program, EngineConfig(edge_backend=edge_backend))
    host_args = (sess.device_graph(cfg), sess._layout_arg(program, eb, cfg),
                 canonical_params(params),
                 sess._warm_arg(program, None, False, cfg))
    specs = jax.tree.map(lambda x: _spec(x, sharding), host_args)
    runner = make_sim_runner(program, cfg, sess.slot_capacity,
                             warm_start=True)
    return jax.jit(runner).lower(*specs).compile()


def _shard_runner():
    """The served shard_map runner on a described 2x2 v5e, one partition a
    chip, as the four-chip cell runs it (``pallas_windows``, warm input,
    traced params), compiled."""
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.core import make_bsp_runner
    from repro.core.engine import shard_specs
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    mesh = Mesh(np.array(topo.devices), ("sub",))
    g = kronecker_graph(10, seed=0, weighted=True)
    sess = GraphSession.from_graph(g, n_parts=4, partitioner="cdbh")
    program, params = SSSP(), canonical_params({"source": 0})
    eb, cfg = normalize_edge_backend(program, EngineConfig(
        backend="shard_map", edge_backend="pallas_windows"))
    specs = shard_specs(cfg)
    args = (sess.device_graph(), sess._layout_arg(program, eb, cfg), params,
            sess._warm_arg(program, None, False, cfg))
    places = (specs.graph, specs.windows,
              jax.tree.map(lambda _: P(), params), specs.warm)
    sds = jax.tree.map(lambda x, sp: _spec(x, NamedSharding(mesh, sp)),
                       args, places, is_leaf=lambda x: isinstance(x, P))
    runner = make_bsp_runner(program, mesh, cfg, sess.slot_capacity,
                             warm_start=True)
    return jax.jit(runner).lower(*sds).compile()


@pytest.mark.parametrize("program,params,edge_backend", [
    (SSSP(), {"source": 0}, "pallas_windows"),
    (ConnectedComponents(), None, "pallas_tiles")])
def test_sim_runner_compiles(one_chip, program, params, edge_backend):
    """The whole served runner — superstep loop, SBS exchange, kernel
    sweeps — as ``GraphSession`` builds it, over a Kronecker graph."""
    compiled = _sim_runner(one_chip, program, params, edge_backend)
    assert _has_kernel(compiled)
    assert compiled.memory_analysis() is not None


def test_window_product_fits_at_scale_22(one_chip):
    """The windowed product at the padded shapes of a Graph500 scale-22
    session in 16 partitions (``chip_smoke.py``): it must compile, and its
    temporaries must stay far below the chip's 16 GiB — a block layout
    with a unit second-minor dim, which XLA pads 8x, needed 10 GiB here."""
    from repro.core.api import SemiringSweep
    from repro.core.engine import _window_product
    from repro.core.layouts import WindowBlock
    P, v_max, e_max, b_max = 16, 1 << 20, 1 << 23, 1 << 15

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    blk = WindowBlock(s((P, e_max), jnp.int32), s((P, b_max * BE), jnp.int32),
                      s((P, b_max), jnp.int32))
    spec = SemiringSweep("min_plus", "weight")
    compiled = jax.jit(lambda b, v, es, ew: _window_product(
        b, v, spec, v_max, es, ew)).lower(
        blk, s((P, v_max, 1), jnp.float32), s((P, e_max), jnp.int32),
        s((P, e_max), jnp.float32)).compile()
    assert _has_kernel(compiled)
    assert compiled.memory_analysis().temp_size_in_bytes < 2 * 2**30


def test_window_product_scatter_sorts_nothing(one_chip):
    """One partition of the four-chip scale-20 cell (2**23 edge slots,
    2**15 blocks): the scatter into slot order is promised sorted, unique
    slots, so the compiled product holds no sort of them — on a v5e that
    sort took 7.5% of a chip's busy time."""
    from repro.core.api import SemiringSweep
    from repro.core.engine import _window_product
    from repro.core.layouts import WindowBlock
    v_max, e_max, b_max = 1 << 19, 1 << 23, 1 << 15

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    blk = WindowBlock(s((e_max,), jnp.int32), s((b_max * BE,), jnp.int32),
                      s((b_max,), jnp.int32))
    spec = SemiringSweep("min_plus", "weight")
    compiled = jax.jit(lambda b, v, es, ew: _window_product(
        b, v, spec, v_max, es, ew)).lower(
        blk, s((v_max, 1), jnp.float32), s((e_max,), jnp.int32),
        s((e_max,), jnp.float32)).compile()
    assert _has_kernel(compiled)
    text = compiled.as_text()
    assert " scatter(" in text
    assert " sort(" not in text


def test_shard_runner_compiles_on_four_chips(one_chip):
    """The served shard_map runner on a described 2x2 v5e, one partition a
    chip, as the four-chip cell runs it (``pallas_windows``, warm input,
    traced params): the per-partition local bound and the termination vote
    lower to one program per chip with its all-reduces."""
    compiled = _shard_runner()
    assert _has_kernel(compiled)
    assert "all-reduce" in compiled.as_text()


#: instructions of each kind in the compiled text of the two served runners
#: above, as commit 38c2779 compiles them: the simulator's (SSSP on
#: ``pallas_windows``, the one-chip cells' form) launches the windowed
#: kernel in the first sweep and in the local loop, under a ``lax.map`` over
#: the partitions; the shard_map runner's the same kernels per chip, with
#: the boundary ``pmin`` and the vote's two psums as all-reduces
OP_PATTERNS = {"kernel": r'custom_call_target="tpu_custom_call"',
               "all-reduce": r" all-reduce(?:-start)?\(",
               "scatter": r" scatter\(", "while": r" while\(",
               "sort": r" sort\("}
OP_COUNTS = {
    "sim": {"kernel": 2, "all-reduce": 0, "scatter": 3, "while": 4,
            "sort": 0},
    "shard": {"kernel": 2, "all-reduce": 2, "scatter": 3, "while": 2,
              "sort": 0}}


@pytest.mark.parametrize("runner", ["sim", "shard"])
def test_runner_op_counts(one_chip, runner):
    """Both runners run the one superstep and loop of ``core/engine.py``:
    their compiled programs hold as many kernel launches, all-reduces,
    scatters and while loops as the runners of commit 38c2779, and no
    sort."""
    import re
    compiled = _sim_runner(one_chip, SSSP(), {"source": 0},
                           "pallas_windows") if runner == "sim" \
        else _shard_runner()
    text = compiled.as_text()
    assert {k: len(re.findall(p, text))
            for k, p in OP_PATTERNS.items()} == OP_COUNTS[runner]
