"""Calibrated edge-backend selection for ``EngineConfig.edge_backend='auto'``.

The three edge-compute backends trade memory traffic very differently
(docs/ARCHITECTURE.md, "Edge-compute backends"):

  - ``coo``            gathers a value per *resident edge* and scatters it
                       into a dense per-vertex aggregate (``scatter-min`` /
                       ``scatter-add`` through HBM);
  - ``pallas_tiles``   pays a fixed ~64 KiB per 128x128 tile regardless of
                       how empty it is — a coverage floor of ``n_dst_tiles``
                       tiles even for a near-empty partition;
  - ``pallas_windows`` pays per occupied 512-edge block plus a per-window
                       epilogue, and reduces by destination inside 128-row
                       windows instead of scattering into vertex slots.

Which one wins depends on the chip *and* on the sweep: the chip ranks them
one way for float32 BFS/SSSP and the other way for int32 CC. So ``'auto'``
keeps one **calibration table per sweep key** — (device kind, engine
backend ``sim`` | ``shard_map``, ``SemiringSweep.semiring``,
``SemiringSweep.edge_values``, program dtype) — calibrated lazily the first
time a session needs the key, and cached on disk.

A calibration sweep costs a grid of synthetic partitions at the engine's
sizes (``GRID``: 4,096 to 65,536 vertex slots, 32 k to 2 M edges, R-MAT
destination degrees with permuted labels, like a Graph500 Kronecker
partition) on each backend and fits per-unit costs (seconds per COO edge,
per dense tile, per window block, ...) by non-negative least squares. On a
TPU each point times the engine's own product functions, in the form the
key's runner executes them: for the simulator the vmapped
``coo_semiring_product`` over a stack of ``GRID_PARTS`` partitions, the
stacked ``_window_product`` and ``_tile_product``; for ``shard_map`` their
per-partition forms. Off the TPU the point costs are *modeled* roofline
bytes over HBM bandwidth (interpret-mode wall-clocks are meaningless
there), which is deterministic by construction and makes cached replay and
the calibration tests exact. A ``pallas_tiles`` point whose dense tiles
would not fit ``TILE_BUDGET_BYTES`` costs ``inf`` and is not built.

The policy is then a pure argmin over per-partition unit counts the layout
geometry already tracks (``edges_per_part``, ``EdgeLayouts.n_tiles``,
``EdgeLayouts.n_blocks``): no tracing, no device work, same answer for the
same (table, geometry). ``engine.resolve_partition_backends`` is the
engine-facing entry; sessions pin the resulting assignment per sweep key
and shape bucket so in-bucket streaming growth can never flip a
partition's backend mid-session (zero-retrace contract, docs/API.md
"Caching rules").

Cache location: ``$DRONE_AUTOTUNE_DIR`` when set, else
``<checkout>/.cache/autotune/`` (``repro.caches``), one JSON per (sweep
key, schema version). Delete the file (or bump ``SCHEMA_VERSION``) to force
recalibration; a corrupt or stale-schema file is recalibrated, never
trusted.
"""
from __future__ import annotations

import dataclasses
import functools
import json
import os
import re
import time
import types
from typing import Dict, NamedTuple, Optional, Sequence, Tuple

import numpy as np

from repro.core.layouts import (DEFAULT_BLOCK_EDGES, TileBlock, WindowBlock,
                                build_edge_layouts)
from repro.core.subgraph import ShapePolicy
from repro.kernels.bsp_spmv import TM, TN
from repro.kernels.segment_combine import W

__all__ = ["CalibrationTable", "SweepKey", "sweep_key", "calibrate",
           "get_table", "load_table", "save_table", "table_path",
           "pick_backends", "BACKEND_ORDER", "SCHEMA_VERSION"]

SCHEMA_VERSION = 3

#: argmin tie-break order — fixed so replayed tables pick identically.
BACKEND_ORDER: Tuple[str, ...] = ("coo", "pallas_windows", "pallas_tiles")

#: roofline constant of the modeled path (a v5e's HBM bandwidth)
HBM_BW = 819e9

#: calibration grid: (vertex slots, edges) of one partition. It spans a
#: scale-16 Graph500 partition in 16 parts (16,384 slots, ~131 k edges) to
#: a scale-20 one (65,536 slots, ~2 M edges); vertex and edge counts vary
#: independently so the per-edge and per-vertex costs are identifiable.
GRID: Tuple[Tuple[int, int], ...] = (
    (4096, 32768), (16384, 131072), (16384, 524288), (65536, 524288),
    (65536, 2097152))
#: partitions in a measured simulator stack (costs are per partition)
GRID_PARTS = 4
#: the dense tiles of one stack may take at most this many device bytes
#: (a sixteenth of a v5e's 16 GB); past it ``pallas_tiles`` costs inf
TILE_BUDGET_BYTES = 1 << 30
_GRID_SEED = 0xD120


class SweepKey(NamedTuple):
    """What a calibration table is specific to."""
    platform: str       # jax ``device_kind`` ("TPU v5 lite"; "cpu")
    engine: str         # EngineConfig.backend: 'sim' | 'shard_map'
    semiring: str       # SemiringSweep.semiring
    edge_values: str    # SemiringSweep.edge_values
    dtype: str          # numpy name of the program dtype ('float32')

    @property
    def itemsize(self) -> int:
        return np.dtype(self.dtype).itemsize


def sweep_key(program, engine: str,
              platform: Optional[str] = None) -> SweepKey:
    """The calibration key of ``program``'s generated sweep under the
    ``engine`` backend on ``platform`` (default: the current device)."""
    spec = program.sweep_spec
    return SweepKey(platform or _platform(), engine, spec.semiring,
                    spec.edge_values, np.dtype(program.dtype).name)


def _tile_stack_bytes(n_tiles, n_parts: int, itemsize: int):
    """Device bytes of ``n_parts`` partitions' dense tiles, padded to
    ``n_tiles`` each (the stacked ``TileBlock``)."""
    return np.asarray(n_tiles, np.float64) * n_parts * TM * TN * itemsize


# --------------------------------------------------------------------------- #
@dataclasses.dataclass
class CalibrationTable:
    """One sweep key's calibrated per-unit backend costs + the grid points
    they were fitted from (kept for the ``--crossover`` benchmark and for
    determinism tests — same key, same schema => byte-identical JSON).
    """

    key: SweepKey
    source: str                       # 'modeled' | 'measured'
    points: list                      # list of per-point dicts (JSON rows)
    unit_costs: Dict[str, float]      # seconds per unit of work
    seconds: float = 0.0              # wall time the calibration took

    # ------------------------------------------------------------------ #
    def partition_costs(self, *, n_edges, n_vertices: int, n_tiles,
                        n_blocks, n_windows: int,
                        tiles_fit: bool = True) -> Dict[str, np.ndarray]:
        """Predicted per-partition sweep cost (seconds) per backend.

        ``n_edges``/``n_tiles``/``n_blocks`` are [P] unit counts straight
        from the graph and its ``EdgeLayouts`` geometry; ``n_vertices`` and
        ``n_windows`` are the shared padded per-partition constants.
        ``tiles_fit=False`` (the graph's dense tiles exceed the budget)
        prices ``pallas_tiles`` at inf."""
        u = self.unit_costs
        ne = np.asarray(n_edges, np.float64)
        coo = u["coo_edge"] * ne + u["coo_vertex"] * float(n_vertices)
        tiles = u["tile"] * np.asarray(n_tiles, np.float64)
        if not tiles_fit:
            tiles = np.full_like(tiles, np.inf)
        windows = (u["win_block"] * np.asarray(n_blocks, np.float64)
                   + u["win_window"] * float(n_windows)
                   + u["win_edge"] * ne)
        return {"coo": coo, "pallas_tiles": tiles, "pallas_windows": windows}

    def pick(self, **units) -> Tuple[str, ...]:
        """Per-partition argmin over ``partition_costs`` (ties resolve to
        the earliest entry of ``BACKEND_ORDER`` — deterministic replay)."""
        costs = self.partition_costs(**units)
        mat = np.stack([np.atleast_1d(costs[b]) for b in BACKEND_ORDER])
        return tuple(BACKEND_ORDER[i] for i in np.argmin(mat, axis=0))

    # ------------------------------------------------------------------ #
    def to_json(self) -> str:
        return json.dumps(
            dict(version=SCHEMA_VERSION, key=self.key._asdict(),
                 source=self.source, unit_costs=self.unit_costs,
                 points=self.points, seconds=self.seconds),
            indent=1, sort_keys=True)

    @classmethod
    def from_json(cls, text: str) -> "CalibrationTable":
        d = json.loads(text)
        if d.get("version") != SCHEMA_VERSION:
            raise ValueError(f"autotune table schema {d.get('version')!r} != "
                             f"{SCHEMA_VERSION}")
        return cls(key=SweepKey(**d["key"]), source=d["source"],
                   points=d["points"], unit_costs=d["unit_costs"],
                   seconds=d["seconds"])


# --------------------------------------------------------------------------- #
# the grid
# --------------------------------------------------------------------------- #
def _grid_edges(nv: int, ne: int, seed: int) -> tuple:
    """One synthetic partition: ``ne`` R-MAT edges over ``nv`` vertex slots
    (Graph500 quadrant probabilities, labels permuted, so destination
    degrees are skewed as in a Kronecker partition), dst-sorted ascending
    like ``localize_edges`` output, with weights in [1, 10)."""
    from repro.graphgen.kronecker import rmat_edges
    src, dst = rmat_edges(int(nv).bit_length() - 1, ne, seed=seed)
    order = np.argsort(dst * nv + src)
    w = np.random.default_rng(seed).uniform(1.0, 10.0, ne)
    return (src[order].astype(np.int32), dst[order].astype(np.int32),
            w.astype(np.float32))


def _point_units(nv: int, src: np.ndarray, dst: np.ndarray) -> dict:
    """Unit counts the engine's geometry builders assign one partition
    (coverage fillers and per-window block minima included — the counting
    of ``EdgeLayouts._partition_caps``)."""
    nst, ndt, nw = -(-nv // TN), -(-nv // TM), -(-nv // W)
    tkey = (dst.astype(np.int64) // TM) * nst + src.astype(np.int64) // TN
    rows = np.unique(tkey)
    n_tiles = rows.shape[0] + ndt - np.unique(rows // nst).shape[0]
    counts = np.bincount(dst.astype(np.int64) // W, minlength=nw)
    n_blocks = int(np.maximum(-(-counts // DEFAULT_BLOCK_EDGES), 1).sum())
    return dict(n_vertices=int(nv), n_edges=int(src.shape[0]),
                n_tiles=int(n_tiles), n_blocks=n_blocks, n_windows=int(nw))


@functools.lru_cache(maxsize=1)
def _grid_units() -> tuple:
    """Unit counts of every grid point (deterministic, key-independent)."""
    return tuple(_point_units(nv, *_grid_edges(nv, ne, _GRID_SEED + i)[:2])
                 for i, (nv, ne) in enumerate(GRID))


def _modeled_costs(units: dict, key: SweepKey) -> Dict[str, float]:
    """Roofline-modeled seconds of one partition's sweep per backend (K=1),
    the byte accounting of ``benchmarks/kernel_roofline.py`` at the key's
    item size ``i``: COO streams two int32 indices, a gathered value and
    the read-modify-written aggregate per edge (16 + 2 i bytes) and the
    aggregate per vertex row (2 i); a dense tile streams its values and the
    v/out slices; a window block streams its slot buffer and local dst, a
    window its epilogue, and every edge its message (2 i)."""
    i = float(key.itemsize)
    ne, nv = units["n_edges"], units["n_vertices"]
    coo = (ne * (16.0 + 2.0 * i) + nv * 2.0 * i) / HBM_BW
    tiles = units["n_tiles"] * (TM * TN + TM + TN) * i / HBM_BW
    windows = (units["n_blocks"] * DEFAULT_BLOCK_EDGES * (4.0 + i)
               + units["n_windows"] * W * 2.0 * i + ne * 2.0 * i) / HBM_BW
    return {"coo": coo, "pallas_tiles": tiles, "pallas_windows": windows}


class _Edges(NamedTuple):
    """The edge arrays ``coo_semiring_product`` reads of a DeviceSubgraph."""
    esrc: object
    edst: object
    ew: object
    emask: object


def _measured_costs(nv: int, edges: tuple, key: SweepKey) -> Dict[str, float]:
    """Seconds per partition of one sweep product on each backend, timed on
    the device in the form the key's runner executes it: the simulator's
    stacked forms over ``GRID_PARTS`` copies of the partition, or the
    shard_map body's per-partition forms. Every input is uploaded once,
    explicitly, before the clock starts."""
    import jax

    from repro.core import api, engine

    src, dst, w = edges
    spec = api.SemiringSweep(key.semiring, key.edge_values)
    dtype = np.dtype(key.dtype)
    ne = src.shape[0]
    pg = types.SimpleNamespace(                      # one partition, no pad
        n_parts=1, v_max=nv, e_max=ne, esrc=src[None], edst=dst[None],
        ew=w[None], emask=np.ones((1, ne), bool))
    lay = build_edge_layouts(pg, ShapePolicy.exact(1))
    ids = np.arange(nv)
    vals = np.where(ids % 2 == 0, ids, spec.identity(dtype)).astype(dtype)
    host = dict(edges=_Edges(pg.esrc, pg.edst, pg.ew, pg.emask),
                windows=WindowBlock(lay.eslot, lay.ldst, lay.bwin),
                vals=vals[None, :, None])
    stacked = key.engine == "sim"
    parts = GRID_PARTS if stacked else 1
    tiles_fit = _tile_stack_bytes(lay.n_tiles[0], parts,
                                  dtype.itemsize) <= TILE_BUDGET_BYTES
    if tiles_fit:
        host["tiles"] = TileBlock(
            lay.tile_values(pg, spec.semiring, spec.edge_values, dtype),
            lay.tile_dst, lay.tile_src)
    if stacked:
        host = jax.tree.map(lambda a: np.repeat(a, parts, axis=0), host)
    else:
        host = jax.tree.map(lambda a: a[0], host)
    dev = jax.device_put(host)

    if stacked:
        def coo(d):
            return jax.vmap(
                lambda sg, v: api.coo_semiring_product(sg, spec, v)
            )(d["edges"], d["vals"])
    else:
        def coo(d):
            return api.coo_semiring_product(d["edges"], spec, d["vals"])

    def windows(d):
        return engine._window_product(d["windows"], d["vals"], spec, nv,
                                      d["edges"].esrc, d["edges"].ew)

    def tiles(d):
        return engine._tile_product(d["tiles"], d["vals"], spec, nv)

    def timed(fn) -> float:
        run = jax.jit(fn)
        jax.block_until_ready(run(dev))              # compile + warm
        best = np.inf
        for _ in range(2):
            t0 = time.perf_counter()
            jax.block_until_ready(run(dev))
            best = min(best, time.perf_counter() - t0)
        return best / parts

    return {"coo": timed(coo), "pallas_windows": timed(windows),
            "pallas_tiles": timed(tiles) if tiles_fit else np.inf}


def _fit_unit_costs(points: Sequence[dict]) -> Dict[str, float]:
    """Non-negative least-squares per-unit costs from the grid points. On
    the modeled path the fit is exact (the costs *are* linear in the unit
    counts); on the measured path it smooths launch noise, and no noisy
    point can make a cost negative. ``pallas_tiles`` is fitted on the
    points whose tiles fit (``inf`` when none does)."""
    from scipy.optimize import nnls

    def col(name):
        return np.array([p[name] for p in points], np.float64)

    def fit(cols, y):
        a = np.stack(cols, 1)
        scale = a.max(axis=0)                       # condition the columns
        return nnls(a / scale, y)[0] / scale

    ne, nv, nt = col("n_edges"), col("n_vertices"), col("n_tiles")
    nb, nw = col("n_blocks"), col("n_windows")
    c_coo = fit([ne, nv], col("cost_coo"))
    c_win = fit([nb, nw, ne], col("cost_windows"))
    cost_t = col("cost_tiles")
    ok = np.isfinite(cost_t)
    tile = float(fit([nt[ok]], cost_t[ok])[0]) if ok.any() else np.inf
    return {"coo_edge": float(c_coo[0]), "coo_vertex": float(c_coo[1]),
            "tile": tile, "win_block": float(c_win[0]),
            "win_window": float(c_win[1]), "win_edge": float(c_win[2])}


def _platform() -> str:
    """jax's ``device_kind`` of the first device (e.g. ``"TPU v5 lite"``;
    ``"cpu"`` off-accelerator)."""
    import jax
    return jax.devices()[0].device_kind


def calibrate(key: SweepKey) -> CalibrationTable:
    """Run the calibration sweep for ``key``. Timed on the device when the
    key's platform is the attached TPU; modeled otherwise — pure host
    work."""
    import jax
    t0 = time.perf_counter()
    measured = jax.default_backend() == "tpu" and key.platform == _platform()
    parts = GRID_PARTS if key.engine == "sim" else 1
    points = []
    for i, (nv, ne) in enumerate(GRID):
        units = _grid_units()[i]
        if measured:
            costs = _measured_costs(nv, _grid_edges(nv, ne, _GRID_SEED + i),
                                    key)
        else:
            costs = _modeled_costs(units, key)
            if _tile_stack_bytes(units["n_tiles"], parts, key.itemsize) \
                    > TILE_BUDGET_BYTES:
                costs["pallas_tiles"] = np.inf
        points.append(dict(units, cost_coo=costs["coo"],
                           cost_tiles=costs["pallas_tiles"],
                           cost_windows=costs["pallas_windows"]))
    return CalibrationTable(
        key=key, source="measured" if measured else "modeled",
        points=points, unit_costs=_fit_unit_costs(points),
        seconds=time.perf_counter() - t0 if measured else 0.0)


# --------------------------------------------------------------------------- #
# disk cache
# --------------------------------------------------------------------------- #
def cache_dir() -> str:
    from repro.caches import CACHE_ROOT
    return os.environ.get("DRONE_AUTOTUNE_DIR") or os.path.join(
        CACHE_ROOT, "autotune")


def table_path(key: SweepKey) -> str:
    slug = re.sub(r"[^0-9a-z]+", "_", "_".join(key).lower())
    return os.path.join(cache_dir(), f"autotune_{slug}_v{SCHEMA_VERSION}.json")


def load_table(key: SweepKey) -> Optional[CalibrationTable]:
    path = table_path(key)
    try:
        with open(path, "r", encoding="utf-8") as f:
            table = CalibrationTable.from_json(f.read())
    except FileNotFoundError:
        return None
    except (ValueError, KeyError, TypeError, json.JSONDecodeError) as e:
        # stale schema / corrupt cache: recalibrate rather than trust it
        import logging
        logging.getLogger(__name__).debug(
            "discarding autotune cache %s: %s", path, e)
        return None
    return table if table.key == key else None


def save_table(table: CalibrationTable) -> str:
    path = table_path(table.key)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    tmp = f"{path}.{os.getpid()}.tmp"
    with open(tmp, "w", encoding="utf-8") as f:
        f.write(table.to_json())
    os.replace(tmp, path)
    return path


def get_table(key: SweepKey, *, force: bool = False) -> CalibrationTable:
    """``key``'s calibration table: disk cache first, else calibrate and
    persist. ``force=True`` recalibrates unconditionally."""
    if not force:
        cached = load_table(key)
        if cached is not None:
            return cached
    table = calibrate(key)
    save_table(table)
    return table


# --------------------------------------------------------------------------- #
def pick_backends(table: CalibrationTable, pg, lay) -> Tuple[str, ...]:
    """Per-partition backend assignment for a ``PartitionedGraph`` + its
    ``EdgeLayouts`` geometry — the ``edge_backend='auto'`` policy. Dense
    tiles are realized for the whole stack at its padded ``t_max``, so
    ``pallas_tiles`` is out when that stack exceeds the budget."""
    fit = _tile_stack_bytes(lay.t_max, pg.n_parts, table.key.itemsize) \
        <= TILE_BUDGET_BYTES
    return table.pick(
        n_edges=pg.edges_per_part, n_vertices=pg.v_max,
        n_tiles=lay.n_tiles, n_blocks=lay.n_blocks,
        n_windows=lay.n_windows, tiles_fit=bool(fit))
