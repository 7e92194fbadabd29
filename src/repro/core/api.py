"""DRONE programming API (paper §5.1), adapted to JAX.

The paper exposes ``Compute(g Subgraph, M message) -> vector`` plus
``addPairToVector``/``voteToHalt``. The TPU-native equivalent is a
``VertexProgram``: a pytree-pure description of

  - how to initialize per-partition state                         (init)
  - how to consume merged frontier data at a superstep boundary   (apply_frontier)
  - one local relaxation sweep over the partition                 (sweep)
  - which per-vertex payload to contribute to SBS                 (frontier_out)

Programs whose sweep is a semiring SpMV declare it as a ``SemiringSweep``
spec plus ``sweep_values``/``sweep_fold`` transforms instead of overriding
``sweep``: the base-class ``sweep`` then runs the COO reference product
(``coo_semiring_product``), and the engine can swap in a Pallas kernel
backend (``EngineConfig.edge_backend``) without the program noticing.

The engine (engine.py) runs ``sweep`` each superstep: to a local fixed point
("think like a graph") on a partition that holds interior paths, once on any
other partition when the combiner is ``min``/``max`` (the exchange carries
the paths there); ``mode="vc"`` or ``max_local_iters=1`` sweeps once
everywhere, the vertex-centric baseline. It performs SBS with the program's
combiner, counts changed (key,value) pairs — the paper's network-message
metric — and terminates when no partition emits changes (voteToHalt + no
pending messages).
"""
from __future__ import annotations

import dataclasses
from typing import Any, ClassVar, NamedTuple, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from repro.kernels.ref import combine_identity as _combine_identity


class DeviceSubgraph(NamedTuple):
    """Per-partition device arrays (one shard; no leading P dim).

    ``v_max``/``e_max`` are padded capacities chosen by a ``ShapePolicy``
    (core/subgraph.py) — content fills a prefix, masks mark the rest. The
    engine's exchange buffer may likewise be built on an over-provisioned
    slot count >= the actual ``n_slots``: rows at and above the actual
    count (including every vertex's ``slot`` sentinel) only ever hold the
    combiner identity, which is what lets a serving session bucket all four
    padded dims without retracing on in-bucket growth.
    """
    esrc: jnp.ndarray     # [e_max] int32 local src
    edst: jnp.ndarray     # [e_max] int32 local dst (ascending)
    ew: jnp.ndarray       # [e_max] f32
    emask: jnp.ndarray    # [e_max] bool
    slot: jnp.ndarray     # [v_max] int32 frontier slot (n_slots if none)
    vmask: jnp.ndarray    # [v_max] bool
    vid32: jnp.ndarray    # [v_max] int32 global vertex id (INT32_MAX pad)
    is_frontier: jnp.ndarray  # [v_max] bool — vertex replicated elsewhere
    out_deg: jnp.ndarray  # [v_max] f32 full out-degree
    in_deg: jnp.ndarray   # [v_max] f32 full in-degree
    is_master: jnp.ndarray  # [v_max] bool
    local_paths: jnp.ndarray  # [] bool — most edges join interior vertices
    vlabel: Optional[jnp.ndarray] = None  # [v_max] int32

    @property
    def v_max(self) -> int:
        return self.vmask.shape[-1]

    @property
    def e_max(self) -> int:
        return self.emask.shape[-1]

    @property
    def frontier(self) -> jnp.ndarray:
        """[v_max] bool — valid vertices that have an SBS slot."""
        return self.vmask & self.is_frontier

    @property
    def internal(self) -> jnp.ndarray:
        """[v_max] bool — valid vertices living only in this partition."""
        return self.vmask & ~self.is_frontier


# The engine's supported (combiner, dtype) envelope. Values delegate to the
# one generic implementation in kernels/ref.py (the kernels share it), so
# identity semantics cannot silently diverge between the COO and Pallas
# paths; the dict itself stays the strict allowlist the error message names.
COMBINER_IDENTITY = {
    (c, jnp.dtype(d)): _combine_identity(c, d)
    for c in ("min", "max", "sum")
    for d in (jnp.float32, jnp.int32)
}


def combiner_identity(combiner: str, dtype: Any) -> np.generic:
    try:
        return COMBINER_IDENTITY[(combiner, jnp.dtype(dtype))]
    except KeyError:
        supported = ", ".join(
            f"({c!r}, {d.name})" for c, d in sorted(
                COMBINER_IDENTITY, key=lambda k: (k[0], k[1].name)))
        raise ValueError(
            f"no combiner identity for (combiner={combiner!r}, "
            f"dtype={jnp.dtype(dtype).name}); supported (combiner, dtype) "
            f"pairs: {supported}") from None


@dataclasses.dataclass(frozen=True)
class SemiringSweep:
    """Declarative local-sweep spec: the partition-local relaxation is a
    semiring SpMV over the partition's adjacency (kernels/ref.py):

      min_plus    agg[d] = min_e  vals[src(e)] + ev(e)     (SSSP relax, CC
                  min-label propagation with ev = 0)
      plus_times  agg[d] = sum_e  vals[src(e)] * ev(e)     (PageRank push
                  with ev = 1; vals carry the alpha/out_deg rate)

    ``edge_values`` names the edge-value map ``ev`` declaratively
    (``'weight'`` | ``'zero'`` | ``'one'``) so edge-compute backends can
    bake it into device layouts at assembly time (core/layouts.py). The
    vertex-side pre/post transforms around the product are the program's
    ``sweep_values``/``sweep_fold`` methods.

    A program that publishes a spec (``sweep_spec``) gets its ``sweep``
    generated: the engine routes the product through the backend selected
    by ``EngineConfig.edge_backend`` — COO gather/scatter, dense Pallas
    tiles, or windowed Pallas combine — while pre/post transforms and the
    changed-count stay the program's own code. Programs whose sweep does
    not fit the shape (graph simulation's label-indexed joins, or anything
    stateful per edge) leave ``sweep_spec`` as None and override ``sweep``
    directly; they always run on the COO path.
    """

    semiring: str                    # 'min_plus' | 'plus_times'
    edge_values: str = "weight"      # 'weight' | 'zero' | 'one'

    _SEMIRINGS: ClassVar[Tuple[str, ...]] = ("min_plus", "plus_times")
    _EDGE_VALUES: ClassVar[Tuple[str, ...]] = ("weight", "zero", "one")

    def __post_init__(self) -> None:
        if self.semiring not in self._SEMIRINGS:
            raise ValueError(f"SemiringSweep.semiring={self.semiring!r}: "
                             f"allowed values are {self._SEMIRINGS}")
        if self.edge_values not in self._EDGE_VALUES:
            raise ValueError(
                f"SemiringSweep.edge_values={self.edge_values!r}: allowed "
                f"values are {self._EDGE_VALUES}")

    @property
    def combiner(self) -> str:
        """The reduce-by-destination combiner of the semiring's 'addition'."""
        return "min" if self.semiring == "min_plus" else "sum"

    def identity(self, dtype: Any) -> np.generic:
        """Absorbing element absent edges contribute (inf / int-max / 0)."""
        return combiner_identity(self.combiner, dtype)


def coo_semiring_product(sg: "DeviceSubgraph", spec: SemiringSweep,
                         vals: jnp.ndarray) -> jnp.ndarray:
    """The reference edge-compute backend: one semiring product over the
    partition's COO edge list (dense gather + segment scatter). This is
    bit-for-bit the historical hand-rolled sweep body of SSSP/CC/PageRank;
    the Pallas backends (engine.py) must match it exactly for ``min_plus``
    and to float tolerance for ``plus_times``.

    ``vals`` is [v_max] or [v_max, K]; returns an aggregate of the same
    shape (identity where a vertex has no in-edge).
    """
    ident = spec.identity(vals.dtype)
    if spec.edge_values == "weight":
        ev = sg.ew.astype(vals.dtype)
    elif spec.edge_values == "zero":
        ev = jnp.zeros_like(sg.ew, dtype=vals.dtype)
    else:
        ev = jnp.ones_like(sg.ew, dtype=vals.dtype)
    sv = vals[sg.esrc]                               # [e_max(, K)]
    if vals.ndim == 2:
        ev = ev[:, None]
        emask = sg.emask[:, None]
    else:
        emask = sg.emask
    cand = sv + ev if spec.semiring == "min_plus" else sv * ev
    cand = jnp.where(emask, cand, ident)
    agg = jnp.full(vals.shape, ident, vals.dtype)
    if spec.semiring == "min_plus":
        return agg.at[sg.edst].min(cand)
    return agg.at[sg.edst].add(cand)


@dataclasses.dataclass
class VertexProgram:
    """Base class. Subclasses implement the four methods below.

    combiner:    'min' | 'sum' | 'max' — the SBS Aggregate operator (§4.3).
    payload:     K, width of the per-vertex exchanged vector. Scalar algos
                 use K=1; graph simulation uses K=|V_Q|.
    dtype:       dtype of the exchanged payload.
    delta_based: True if frontier_out is a *delta* (sum-combined, e.g. the
                 PageRank accumulator); False if it is the value itself
                 (min/max-combined, e.g. CC labels / SSSP distances).
    tol:         significance threshold for float change detection.
    monotone:    True if per-vertex values only ever tighten under the
                 combiner (SSSP/MSSP/CC). Such programs can warm-start from a
                 previous converged result after graph growth: seeding old
                 values is always sound because extra edges can only improve
                 them further. Non-monotone programs (PageRank) must cold
                 start — the engine enforces that fallback on both backends
                 (the simulator seeds host-side; shard_map threads a sharded
                 warm block into ``warm_init`` on-device).
    value_key:   state entry holding the per-vertex values ``warm_init``
                 tightens (required when ``monotone``).
    """

    combiner: str = "min"
    payload: int = 1
    dtype: Any = jnp.float32
    delta_based: bool = False
    tol: float = 0.0
    monotone: bool = False
    value_key: Optional[str] = None

    # Which delta polarity preserves monotonicity (and therefore warm-start
    # soundness). ``'inserts'``: adding edges only tightens values (SSSP/CC/
    # BFS/LP — any deletion invalidates warm state). ``'deletes'``: removing
    # edges only tightens values (the k-core peel: edges can only disappear
    # from a vertex's support, so previously-peeled vertices stay peeled —
    # any insertion invalidates warm state). A serving session keeps a warm
    # entry across a flush only when every applied op matches the program's
    # polarity (session.py `_on_flush`); the low-level engines trust the
    # caller (`run_sim(init_state=...)` docs).
    warm_under: ClassVar[str] = "inserts"

    # Edge-compute backends this program's ``sweep`` can run on. ``None``
    # (the default) means "derive from the sweep kind": declarative
    # ``sweep_spec`` programs support every backend (the engine generates
    # their product); programs that *override* ``sweep`` must declare the
    # backends their hand-rolled code actually implements — today that is
    # ``("coo",)`` for all shipped custom sweeps — or
    # ``engine.resolve_edge_backend`` refuses to run them at all rather
    # than silently routing them onto a backend they ignore.
    supports_edge_backends: ClassVar[Optional[Tuple[str, ...]]] = None

    # -------------------------------------------------------------- #
    def init(self, sg: DeviceSubgraph, params: Any, ec: Any) -> Any:
        """Build per-partition state. ``ec`` is the EdgeCombine context for
        merging any edge-derived reductions (see engine.EdgeCombine)."""
        raise NotImplementedError

    def apply_frontier(self, sg: DeviceSubgraph, params: Any, state: Any,
                       merged: jnp.ndarray) -> Tuple[Any, jnp.ndarray]:
        """Consume merged [v_max, K] (identity at non-frontier rows).
        Returns (state, n_changed:int32)."""
        raise NotImplementedError

    # ---- local sweep: declarative spec or hand-rolled override -------- #
    # Class-level (ClassVar, not a dataclass field): the spec is part of a
    # program's *type*, like its method overrides — per-instance knobs that
    # change the traced computation belong in dataclass fields instead.
    sweep_spec: ClassVar[Optional[SemiringSweep]] = None

    def sweep_values(self, sg: DeviceSubgraph, params: Any,
                     state: Any) -> jnp.ndarray:
        """Per-vertex values entering the semiring product ([v_max] or
        [v_max, K]); only consulted when ``sweep_spec`` is set."""
        raise NotImplementedError

    def sweep_fold(self, sg: DeviceSubgraph, params: Any, state: Any,
                   agg: jnp.ndarray) -> Tuple[Any, jnp.ndarray]:
        """Fold the product's aggregate (same shape as ``sweep_values``)
        back into state. Returns (state, n_changed:int32)."""
        raise NotImplementedError

    def sweep(self, sg: DeviceSubgraph, params: Any, state: Any,
              ec: Any) -> Tuple[Any, jnp.ndarray]:
        """One local relaxation pass. Returns (state, n_changed:int32).

        Programs with a ``sweep_spec`` inherit this implementation — the
        COO reference backend; ``EngineConfig.edge_backend`` swaps the
        product for a Pallas kernel without touching the program. Programs
        without a spec override the whole method."""
        spec = self.sweep_spec
        if spec is None:
            raise NotImplementedError(
                f"{type(self).__name__} defines neither sweep_spec nor a "
                "sweep override")
        vals = self.sweep_values(sg, params, state)
        agg = coo_semiring_product(sg, spec, vals)
        agg = ec.min(agg) if spec.semiring == "min_plus" else ec.sum(agg)
        return self.sweep_fold(sg, params, state, agg)

    def frontier_out(self, sg: DeviceSubgraph, params: Any,
                     state: Any) -> jnp.ndarray:
        """Per-vertex SBS contribution [v_max, K]."""
        raise NotImplementedError

    def result(self, sg: DeviceSubgraph, params: Any,
               state: Any) -> jnp.ndarray:
        """Per-vertex output [v_max, ...] for collection from masters."""
        raise NotImplementedError

    def warm_init(self, sg: DeviceSubgraph, params: Any, state: Any,
                  warm: jnp.ndarray) -> Any:
        """Fold a previous converged result into a fresh ``init`` state
        (incremental recompute, stream/delta.py). ``warm`` is [v_max, K] in
        this partition's local layout, combiner-identity at padded rows, cast
        to the program dtype by the engine before it reaches either backend
        (host-side under ``run_sim``, inside the shard_map body under
        ``run_shard_map``). Default: tighten ``state[value_key]`` with the
        combiner — correct for any monotone value-typed program."""
        assert self.monotone and self.value_key, \
            "warm_init requires a monotone program with value_key set"
        assert self.combiner in ("min", "max"), \
            "default warm_init only knows min/max tightening; override it"
        cur = state[self.value_key]
        w = warm if cur.ndim == warm.ndim else warm[..., 0]
        op = jnp.minimum if self.combiner == "min" else jnp.maximum
        mask = sg.vmask if cur.ndim == 1 else sg.vmask[..., None]
        state = dict(state)
        state[self.value_key] = jnp.where(mask, op(cur, w.astype(cur.dtype)),
                                          cur)
        return state

    # -------------------------------------------------------------- #
    @property
    def identity(self) -> np.generic:
        return combiner_identity(self.combiner, self.dtype)

    def changed_mask(self, out: jnp.ndarray, last_out: jnp.ndarray) -> jnp.ndarray:
        """[v_max] bool — which vertices would emit a (key,value) pair."""
        if self.delta_based:
            if self.tol > 0:
                return jnp.any(jnp.abs(out) > self.tol, axis=-1)
            return jnp.any(out != 0, axis=-1)
        if self.tol > 0:
            return jnp.any(jnp.abs(out - last_out) > self.tol, axis=-1)
        return jnp.any(out != last_out, axis=-1)
