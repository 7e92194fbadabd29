"""Calibrated edge-backend selection for ``EngineConfig.edge_backend='auto'``.

The three edge-compute backends trade memory traffic very differently
(docs/ARCHITECTURE.md, "Edge-compute backends"):

  - ``coo``            pays ~24 bytes per *resident edge* (gather + scatter
                       through HBM) plus a dense per-vertex aggregate;
  - ``pallas_tiles``   pays a fixed ~64 KiB per 128x128 tile regardless of
                       how empty it is — a coverage floor of ``n_dst_tiles``
                       tiles even for a near-empty partition;
  - ``pallas_windows`` pays per occupied 512-edge block (~8 bytes/slot) plus
                       a per-window epilogue — cheaper than COO once blocks
                       fill, cheaper than tiles until they densify.

The crossover points are machine properties, not constants, so ``'auto'``
derives them from a small **calibration sweep** run once per platform and
cached on disk: synthetic single-partition adjacencies spanning a tile
density grid are pushed through the same geometry builders the engine uses
(``core/layouts.py``), each point is costed per backend, and per-unit costs
(seconds per COO edge, per dense tile, per window block, ...) are fitted by
least squares. Off-TPU the point costs are the *modeled* roofline times of
``benchmarks/kernel_roofline.py`` — interpret-mode wall-clocks are
meaningless there, and the modeled table is deterministic by construction,
which is what makes cached replay and the calibration tests exact. On a
real TPU the sweep times the kernels themselves.

The policy is then a pure argmin over per-partition unit counts the layout
geometry already tracks (``edges_per_part``, ``EdgeLayouts.n_tiles``,
``EdgeLayouts.n_blocks``): no tracing, no device work, same answer for the
same geometry. ``engine.resolve_partition_backends`` is the engine-facing
entry; sessions pin the resulting assignment per shape bucket so in-bucket
streaming growth can never flip a partition's backend mid-session
(zero-retrace contract, docs/API.md "Caching rules").

Cache location: ``$DRONE_AUTOTUNE_DIR`` when set, else
``<checkout>/.cache/autotune/`` (``repro.caches``), one JSON per (device
kind, schema version) — a v5e and a v4 calibrate separately. Delete the
file (or bump ``SCHEMA_VERSION``) to force recalibration; a corrupt or
stale-schema file is recalibrated, never trusted.
"""
from __future__ import annotations

import dataclasses
import json
import os
import re
from typing import Dict, Optional, Sequence, Tuple

import numpy as np

from repro.core.layouts import _tile_geometry, _window_geometry
from repro.kernels.bsp_spmv import TM, TN
from repro.kernels.segment_combine import W

__all__ = ["CalibrationTable", "calibrate", "get_table", "load_table",
           "save_table", "table_path", "pick_backends", "BACKEND_ORDER",
           "SCHEMA_VERSION"]

SCHEMA_VERSION = 2

#: argmin tie-break order — fixed so replayed tables pick identically.
BACKEND_ORDER: Tuple[str, ...] = ("coo", "pallas_windows", "pallas_tiles")

#: roofline constants shared with benchmarks/kernel_roofline.py
HBM_BW = 819e9          # bytes/s
DEFAULT_BLOCK_EDGES = 512

#: calibration grid: (n_vertices, target tile density) pairs. Two vertex
#: counts make the COO per-edge/per-vertex costs separately identifiable;
#: the density axis spans the ultra-sparse -> dense crossover region.
GRID_NV: Tuple[int, ...] = (256, 512)
GRID_DENSITY: Tuple[float, ...] = (0.0005, 0.002, 0.01, 0.05, 0.2, 0.6)
_GRID_SEED = 0xD120


# --------------------------------------------------------------------------- #
@dataclasses.dataclass
class CalibrationTable:
    """One platform's calibrated per-unit backend costs + the sweep points
    they were fitted from (kept for the ``--crossover`` benchmark and for
    determinism tests — same platform, same schema => byte-identical JSON).
    """

    platform: str
    source: str                       # 'modeled' | 'measured'
    points: list                      # list of per-point dicts (JSON rows)
    unit_costs: Dict[str, float]      # seconds per unit of work

    # ------------------------------------------------------------------ #
    def partition_costs(self, *, n_edges, n_vertices: int, n_tiles,
                        n_blocks, n_windows: int) -> Dict[str, np.ndarray]:
        """Predicted per-partition sweep cost (seconds) per backend.

        ``n_edges``/``n_tiles``/``n_blocks`` are [P] unit counts straight
        from the graph and its ``EdgeLayouts`` geometry; ``n_vertices`` and
        ``n_windows`` are the shared padded per-partition constants."""
        u = self.unit_costs
        ne = np.asarray(n_edges, np.float64)
        coo = u["coo_edge"] * ne + u["coo_vertex"] * float(n_vertices)
        tiles = u["tile"] * np.asarray(n_tiles, np.float64)
        windows = (u["win_block"] * np.asarray(n_blocks, np.float64)
                   + u["win_window"] * float(n_windows)
                   + u["win_edge"] * ne)
        return {"coo": coo, "pallas_tiles": tiles, "pallas_windows": windows}

    def pick(self, *, n_edges, n_vertices: int, n_tiles, n_blocks,
             n_windows: int) -> Tuple[str, ...]:
        """Per-partition argmin over ``partition_costs`` (ties resolve to
        the earliest entry of ``BACKEND_ORDER`` — deterministic replay)."""
        costs = self.partition_costs(
            n_edges=n_edges, n_vertices=n_vertices, n_tiles=n_tiles,
            n_blocks=n_blocks, n_windows=n_windows)
        mat = np.stack([np.atleast_1d(costs[b]) for b in BACKEND_ORDER])
        return tuple(BACKEND_ORDER[i] for i in np.argmin(mat, axis=0))

    # ------------------------------------------------------------------ #
    def to_json(self) -> str:
        return json.dumps(
            dict(version=SCHEMA_VERSION, platform=self.platform,
                 source=self.source, unit_costs=self.unit_costs,
                 points=self.points),
            indent=1, sort_keys=True)

    @classmethod
    def from_json(cls, text: str) -> "CalibrationTable":
        d = json.loads(text)
        if d.get("version") != SCHEMA_VERSION:
            raise ValueError(f"autotune table schema {d.get('version')!r} != "
                             f"{SCHEMA_VERSION}")
        return cls(platform=d["platform"], source=d["source"],
                   points=d["points"], unit_costs=d["unit_costs"])


# --------------------------------------------------------------------------- #
# calibration sweep
# --------------------------------------------------------------------------- #
def _synthetic_edges(nv: int, density: float,
                     seed: int) -> Tuple[np.ndarray, np.ndarray]:
    """A deterministic single-partition adjacency with ~``density``
    occupancy of the nv x nv grid, dst-sorted ascending like
    ``localize_edges`` output."""
    rng = np.random.default_rng(seed)
    ne = int(np.clip(round(density * nv * nv), 1, nv * nv))
    flat = rng.choice(nv * nv, size=ne, replace=False)
    dst, src = flat // nv, flat % nv
    order = np.lexsort((src, dst))
    return src[order].astype(np.int64), dst[order].astype(np.int64)


def _point_units(nv: int, src: np.ndarray, dst: np.ndarray) -> dict:
    """Unit counts the engine's geometry builders would assign this
    adjacency (coverage fillers and per-window block minima included)."""
    ndt = max(-(-nv // TM), 1)
    nst = max(-(-nv // TN), 1)
    nw = max(-(-nv // W), 1)
    td, _ts, _et, _er, _ec = _tile_geometry(src, dst, ndt, nst)
    _es, _ld, _bw, nb = _window_geometry(dst, nw, DEFAULT_BLOCK_EDGES)
    filled = np.unique(dst * np.int64(nv) + src).shape[0]
    return dict(n_vertices=int(nv), n_edges=int(src.shape[0]),
                n_tiles=int(td.shape[0]), n_blocks=int(nb),
                n_windows=int(nw),
                density=filled / float(td.shape[0] * TM * TN))


def _modeled_costs(units: dict) -> Dict[str, float]:
    """Roofline-modeled sweep time per backend (K=1), matching the byte
    accounting of ``benchmarks/kernel_roofline.py``: COO streams ~24 B per
    edge + 8 B per vertex row; a dense tile streams its values + the v/out
    slices; a window block streams its slot buffer + the per-window
    epilogue, and every edge pays the int32 slot read + f32 message."""
    ne, nv = units["n_edges"], units["n_vertices"]
    coo = (ne * 24.0 + nv * 8.0) / HBM_BW
    tiles = units["n_tiles"] * (TM * TN * 4.0 + (TM + TN) * 4.0) / HBM_BW
    windows = (units["n_blocks"] * DEFAULT_BLOCK_EDGES * 8.0
               + units["n_windows"] * W * 8.0 + ne * 8.0) / HBM_BW
    return {"coo": coo, "pallas_tiles": tiles, "pallas_windows": windows}


def _measured_costs(units: dict, src: np.ndarray,
                    dst: np.ndarray) -> Dict[str, float]:
    """Wall-clock the three single-partition reference paths (TPU only —
    interpret-mode CPU times are meaningless and are never recorded)."""
    import time

    import jax
    import jax.numpy as jnp

    from repro.kernels import ops

    nv = units["n_vertices"]
    w = np.ones(src.shape[0], np.float32)
    vals = np.linspace(0.0, 1.0, nv, dtype=np.float32)

    def timed(fn):
        fn()                                       # compile + warm
        best = np.inf
        for _ in range(3):
            t0 = time.perf_counter()
            jax.block_until_ready(fn())
            best = min(best, time.perf_counter() - t0)
        return best

    tl = ops.build_tiles(src, dst, w, n_src_rows=nv, n_dst_rows=nv,
                         semiring="min_plus", dtype=np.float32)
    wl = ops.window_align_edges(dst, n_rows=nv,
                                block_edges=DEFAULT_BLOCK_EDGES)
    # every path is one jitted call over arrays uploaded here, once: the
    # layouts' eager __call__ would time a host->device copy per launch
    tl_dev = jax.device_put({n: getattr(tl, n)
                             for n in ("tiles", "tile_dst", "tile_src")})
    wl_dev = jax.device_put({n: getattr(wl, n) for n in
                             ("order", "edge_slot", "local_dst",
                              "block_window")})
    v, v2, s, d, ew = jax.device_put(
        (vals, vals[:, None], src, dst, w))      # the layouts take [n, K]

    def coo_fn(v_, s_, d_, ew_):
        agg = jnp.full((nv,), jnp.inf, jnp.float32)
        return agg.at[d_].min(v_[s_] + ew_)

    def tiles_fn(v_, arrays):
        return _with_arrays(tl, arrays)(v_)

    def windows_fn(v_, s_, ew_, arrays):
        return _with_arrays(wl, arrays)(v_[s_] + ew_[:, None],
                                        combiner="min")

    coo_jit, tiles_jit, windows_jit = map(jax.jit,
                                          (coo_fn, tiles_fn, windows_fn))
    return {"coo": timed(lambda: coo_jit(v, s, d, ew)),
            "pallas_tiles": timed(lambda: tiles_jit(v2, tl_dev)),
            "pallas_windows": timed(lambda: windows_jit(v2, s, ew, wl_dev))}


def _with_arrays(layout, arrays: dict):
    """A shallow copy of a kernel layout holding ``arrays`` (device arrays,
    or tracers under jit) in place of its host arrays."""
    import copy
    lay = copy.copy(layout)
    vars(lay).update(arrays)
    return lay


def _fit_unit_costs(points: Sequence[dict]) -> Dict[str, float]:
    """Least-squares per-unit costs from the sweep points. On the modeled
    path the regression is exact (the costs *are* linear in the unit
    counts); on the measured path it smooths launch noise. Coefficients are
    clipped at >= 0 so one noisy point can never invert a cost."""
    def fit(cols: np.ndarray, y: np.ndarray) -> np.ndarray:
        coef, *_ = np.linalg.lstsq(cols, y, rcond=None)
        return np.maximum(coef, 0.0)

    ne = np.array([p["n_edges"] for p in points], np.float64)
    nv = np.array([p["n_vertices"] for p in points], np.float64)
    nt = np.array([p["n_tiles"] for p in points], np.float64)
    nb = np.array([p["n_blocks"] for p in points], np.float64)
    nw = np.array([p["n_windows"] for p in points], np.float64)

    c_coo = fit(np.stack([ne, nv], 1),
                np.array([p["cost_coo"] for p in points]))
    c_tile = fit(nt[:, None], np.array([p["cost_tiles"] for p in points]))
    c_win = fit(np.stack([nb, nw, ne], 1),
                np.array([p["cost_windows"] for p in points]))
    return {"coo_edge": float(c_coo[0]), "coo_vertex": float(c_coo[1]),
            "tile": float(c_tile[0]), "win_block": float(c_win[0]),
            "win_window": float(c_win[1]), "win_edge": float(c_win[2])}


def _platform() -> str:
    """The table key: jax's ``device_kind`` of the first device (e.g.
    ``"TPU v5 lite"``; ``"cpu"`` off-accelerator)."""
    import jax
    return jax.devices()[0].device_kind


def calibrate(platform: Optional[str] = None) -> CalibrationTable:
    """Run the calibration sweep for ``platform`` (a device kind; default:
    the current device's). Timed on the device when that is the attached
    TPU; modeled otherwise — pure host work, safe at import-ish time."""
    import jax
    platform = platform or _platform()
    measured = jax.default_backend() == "tpu" and platform == _platform()
    points = []
    for i, nv in enumerate(GRID_NV):
        for j, density in enumerate(GRID_DENSITY):
            src, dst = _synthetic_edges(nv, density,
                                        _GRID_SEED + 97 * i + j)
            units = _point_units(nv, src, dst)
            costs = _measured_costs(units, src, dst) if measured \
                else _modeled_costs(units)
            points.append(dict(units, cost_coo=costs["coo"],
                               cost_tiles=costs["pallas_tiles"],
                               cost_windows=costs["pallas_windows"]))
    return CalibrationTable(platform=platform,
                            source="measured" if measured else "modeled",
                            points=points,
                            unit_costs=_fit_unit_costs(points))


# --------------------------------------------------------------------------- #
# disk cache
# --------------------------------------------------------------------------- #
def cache_dir() -> str:
    from repro.caches import CACHE_ROOT
    return os.environ.get("DRONE_AUTOTUNE_DIR") or os.path.join(
        CACHE_ROOT, "autotune")


def table_path(platform: Optional[str] = None) -> str:
    kind = re.sub(r"[^0-9a-z]+", "_", (platform or _platform()).lower())
    return os.path.join(cache_dir(), f"autotune_{kind}_v{SCHEMA_VERSION}.json")


def load_table(platform: Optional[str] = None) -> Optional[CalibrationTable]:
    path = table_path(platform)
    try:
        with open(path, "r", encoding="utf-8") as f:
            return CalibrationTable.from_json(f.read())
    except FileNotFoundError:
        return None
    except (ValueError, KeyError, json.JSONDecodeError) as e:
        # stale schema / corrupt cache: recalibrate rather than trust it
        import logging
        logging.getLogger(__name__).debug(
            "discarding autotune cache %s: %s", path, e)
        return None


def save_table(table: CalibrationTable) -> str:
    path = table_path(table.platform)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    tmp = path + ".tmp"
    with open(tmp, "w", encoding="utf-8") as f:
        f.write(table.to_json())
    os.replace(tmp, path)
    return path


def get_table(platform: Optional[str] = None, *,
              force: bool = False) -> CalibrationTable:
    """The platform's calibration table: disk cache first, else calibrate
    and persist. ``force=True`` recalibrates unconditionally."""
    if not force:
        cached = load_table(platform)
        if cached is not None:
            return cached
    table = calibrate(platform)
    save_table(table)
    return table


# --------------------------------------------------------------------------- #
def pick_backends(table: CalibrationTable, pg, lay) -> Tuple[str, ...]:
    """Per-partition backend assignment for a ``PartitionedGraph`` + its
    ``EdgeLayouts`` geometry — the ``edge_backend='auto'`` policy."""
    return table.pick(
        n_edges=pg.edges_per_part, n_vertices=pg.v_max,
        n_tiles=lay.n_tiles, n_blocks=lay.n_blocks,
        n_windows=lay.n_windows)
