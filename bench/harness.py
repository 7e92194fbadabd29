"""The benchmark harness: one cell of ``BENCHMARK.json``, one run.

Everything that belongs to one configuration, one traffic mix or one metric
is found by name:

- ``bench/configs/<config>.json``: the deployment (generator and scale,
  partitioner and partition count, chips and mesh axis, ``edge_backend``,
  guarantees and the limits of the comparison);
- ``bench/traffic/<mix>.json``: the mix, as data this file's one driver
  reads (``Driver``): the warm-up steps, the steps of one closed-loop
  iteration, how search keys are drawn and how many answers are checked;
- ``bench/metrics/<metric>.py``: one reader per metric, ``read(run)`` ->
  a number, or None when the run has nothing for it to read.

A run: set-up (generate the graph on the device from ``--seed``, partition it
into a ``GraphSession``, warm up every runner the window uses), then the
window (closed-loop iterations of the mix until ``--seconds`` have passed;
the iteration under way when they pass is finished), then the peak device
memory, then the comparison with the plain reference over the edge list
(after the session is closed), then the metrics. With ``trace`` the window
runs under the profiler and the per-layer metrics are reported, else the
end-to-end ones.
"""
from __future__ import annotations

import dataclasses
import gc
import glob
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
import traceback
from typing import Optional

import jax
import numpy as np

from bench import graph500, reference
from bench.peaks import UnknownDevice
from bench.peaks import peaks as peaks_for
from bench.tracing import Trace

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


class SetupError(RuntimeError):
    """The run cannot measure: no accelerator, too few chips, a device
    without peaks, or a cell whose files are missing."""


# --------------------------------------------------------------------------- #
# the cell, found by name
# --------------------------------------------------------------------------- #
def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_reader(name: str):
    """``read`` of ``bench/metrics/<name>.py``."""
    path = os.path.join(BENCH, "metrics", f"{name}.py")
    if not os.path.isfile(path):
        raise SetupError(f"metric {name!r} has no reader at {path}")
    mod_name = "bench_metric_" + "".join(
        c if c.isalnum() else "_" for c in name)
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list     # [(name, unit, read)]
    per_layer: list


def resolve(workload: str, spec: Optional[dict] = None) -> Cell:
    """The cell ``workload`` of ``BENCHMARK.json`` with its configuration,
    its mix and the readers of the metrics it reports."""
    spec = spec or _json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise SetupError(f"no workload {workload!r}; known: {sorted(cells)}")
    w = cells[workload]
    conf = {c["name"]: c for c in spec["configs"]}[w["config"]]
    config = _json(os.path.join(ROOT, conf["file"]))
    traffic_path = os.path.join(BENCH, "traffic", f"{w['traffic']}.json")
    if not os.path.isfile(traffic_path):
        raise SetupError(f"traffic {w['traffic']!r} has no file")

    def metrics(kind):
        return [(m["name"], m["unit"], load_reader(m["name"]))
                for m in spec[kind]
                if workload in m.get("workloads", [workload])]

    return Cell(workload, int(w["chips"]), config, _json(traffic_path),
                metrics("end_to_end"), metrics("per_layer"))


# --------------------------------------------------------------------------- #
# what a run records, for the readers
# --------------------------------------------------------------------------- #
@dataclasses.dataclass
class Query:
    """One query of the window, from the client's side."""
    program: str
    key: Optional[int]          # search key (BFS/SSSP), None for CC
    batches: int                # insert batches flushed before it
    latency_s: float            # call to the global answer on the host
    fresh_s: Optional[float]    # first update of its iteration to answer
    flush_s: Optional[float]    # update + flush of its iteration
    processed_edges: int        # ExecutionStats.processed_edges
    compile_s: float            # ExecutionStats.compile_time (0 on a hit)
    supersteps: int = 0         # ExecutionStats.supersteps
    answer: Optional[np.ndarray] = None
    error: Optional[str] = None
    component_edges: int = 0    # undirected edges in the key's component


@dataclasses.dataclass
class Run:
    cell: Cell
    setup_s: float
    partition_s: float
    compile_s: float
    window_s: float
    queries: list
    resident_edges: int
    window_misses: int
    batches: list = dataclasses.field(default_factory=list)
    trace: Optional[Trace] = None
    peaks: Optional[dict] = None


# --------------------------------------------------------------------------- #
# the one traffic driver
# --------------------------------------------------------------------------- #
def span(name: str):
    """A host span ``bench/<name>`` in the profiler's trace (free when no
    trace is being taken)."""
    return jax.profiler.TraceAnnotation("bench/" + name)


class Driver:
    """Runs a mix's steps against a session: ``query`` (a program, its
    search key and warm mode), ``update`` (an insert batch drawn from the
    graph's R-MAT distribution) and ``flush``. Everything drawn comes from
    the run's seed: the search keys on one stream, insert batches on
    another. Search keys are drawn as Graph500 draws them (``"pick":
    "degree_ge_1"``): distinct, uniform among the vertices with at least
    one edge, and searched in the order drawn."""

    def __init__(self, traffic: dict, config: dict, graph, seed: int):
        self.traffic = traffic
        self.config = config
        n, lo, hi, _, perm = graph
        self.scale = int(np.log2(n))
        self.perm = perm
        deg = np.bincount(lo, minlength=n) + np.bincount(hi, minlength=n)
        if (traffic["loop"], traffic["clients"]) != ("closed", 1):
            raise SetupError("the driver runs one closed-loop client")
        keys = traffic.get("keys", {"pick": "degree_ge_1", "count": 0})
        if keys["pick"] != "degree_ge_1":
            raise SetupError(f"unknown key pick {keys['pick']!r}")
        self.keys = [int(k) for k in np.random.default_rng([seed, 1]).choice(
            np.flatnonzero(deg > 0), int(keys["count"]), replace=False)]
        lone = np.flatnonzero(deg == 0)
        self.isolated = int(lone[0]) if lone.size else int(np.argmin(deg))
        self.batch_rng = np.random.default_rng([seed, 2])
        self.batches: list = []        # (u, v) in the order applied
        self.next_key = 0

    def _cfg(self):
        from repro.core import EngineConfig
        axis = self.config.get("mesh_axis")
        kw = {"subgraph_axes": (axis,)} if axis else {}
        return EngineConfig(edge_backend=self.config["edge_backend"], **kw)

    def source(self, how: str) -> Optional[int]:
        if how == "key":
            k = self.keys[self.next_key % len(self.keys)]
            self.next_key += 1
            return k
        if how == "isolated":
            return self.isolated
        return None

    def query(self, sess, step: dict) -> Query:
        import repro.algos as algos
        name = step["program"]
        prog = getattr(algos, name)()
        key = self.source(step.get("source", "none"))
        params = None if key is None else {"source": key}
        fill = -1 if name == "ConnectedComponents" else np.float32(np.inf)
        t0 = time.perf_counter()
        try:
            with span("query"):
                res, st = sess.query(prog, params, warm=step["warm"],
                                     cfg=self._cfg())
            with span("result_copy"):
                got = sess.pg.collect(res, fill=fill)
        except Exception:
            traceback.print_exc()
            return Query(name, key, len(self.batches),
                         time.perf_counter() - t0, None, None, 0, 0.0,
                         error=traceback.format_exc(limit=1))
        return Query(name, key, len(self.batches), time.perf_counter() - t0,
                     None, None, int(st.processed_edges),
                     float(st.compile_time), int(st.supersteps), answer=got)

    def run_steps(self, sess, steps: list) -> list:
        """One pass over ``steps``; the queries it made, each with the
        freshness and flush time of its pass."""
        out, t_first_update, t_flush = [], None, 0.0
        for step in steps:
            op = step["op"]
            if op == "query":
                t_q = time.perf_counter()
                q = self.query(sess, step)
                if t_first_update is not None:
                    q.fresh_s = t_q + q.latency_s - t_first_update
                    q.flush_s = t_flush
                out.append(q)
                continue
            t0 = time.perf_counter()
            if op == "update":
                u, v, w = graph500.rmat_batch(self.batch_rng, self.scale,
                                              self.perm, int(step["adds"]))
                t0 = time.perf_counter()
                t_first_update = t_first_update or t0
                with span("update"):
                    sess.update(adds=(np.r_[u, v], np.r_[v, u],
                                           np.r_[w, w]))
                self.batches.append((u, v))
            elif op == "flush":
                with span("flush"):
                    sess.flush()
            else:
                raise SetupError(f"unknown traffic op {op!r}")
            t_flush += time.perf_counter() - t0
        return out


# --------------------------------------------------------------------------- #
# correctness: every answer checked (or a seeded sample), against scipy
# --------------------------------------------------------------------------- #
def check(run: Run, graph, seed: int) -> tuple:
    """(readings {name: value}, failed answers): each number is the worst
    over the checked answers (a count is summed)."""
    n, lo, hi, w, _ = graph
    limits = run.cell.config["limits"]
    src, dst, wt = graph500.both_directions(lo, hi, w)
    ref = reference.Reference(n, src, dst, wt)
    del src, dst, wt
    labels = ref.cc()
    comp_edges = reference.component_edges(labels, lo)
    for q in run.queries:
        if q.key is not None:
            q.component_edges = int(comp_edges[labels[q.key]])
    answered = [q for q in run.queries if q.answer is not None]
    k = int(run.cell.traffic["check"]["max_answers"])
    if len(answered) > k:
        pick = np.random.default_rng([seed, 3]).choice(len(answered), k,
                                                       replace=False)
        answered = [answered[i] for i in sorted(pick)]
    readings: dict = {"unanswered": sum(q.error is not None
                                        for q in run.queries)}
    failed = readings["unanswered"]
    cur_labels, has_edge, applied = labels, ref.degree > 0, 0
    for q in answered:
        if q.program == "ConnectedComponents":
            while applied < q.batches:
                u, v = run.batches[applied]
                cur_labels = reference.merge_components(cur_labels, u, v)
                has_edge = has_edge.copy()
                has_edge[u] = has_edge[v] = True
                applied += 1
            r = reference.cc_readings(q.answer, cur_labels, has_edge)
        elif q.program == "BFS":
            r = reference.traversal_readings("bfs", q.answer,
                                             ref.bfs(q.key))
        else:
            r = reference.traversal_readings("sssp", q.answer,
                                             ref.sssp(q.key))
        failed += any(v > limits[name] for name, v in r.items())
        for name, v in r.items():
            readings[name] = (max(readings.get(name, 0.0), v)
                              if isinstance(v, float)
                              else readings.get(name, 0) + v)
    readings["answers_checked"] = len(answered)
    return readings, failed


# --------------------------------------------------------------------------- #
# one run
# --------------------------------------------------------------------------- #
def configure_caches() -> None:
    """JAX's persistent compilation cache where the program keeps it
    (``JAX_COMPILATION_CACHE_DIR`` if set, else ``<checkout>/.cache/jax``),
    with every program cached, so that only a cell's first run in a
    checkout compiles. The edge-backend calibration table stays where the
    program keeps it (``DRONE_AUTOTUNE_DIR``, else ``.cache/autotune``)."""
    from repro.caches import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def device_info(chips: int) -> dict:
    devs = jax.devices()
    d0 = devs[0]
    if d0.platform != "tpu":
        raise SetupError(f"no TPU: JAX found {d0.platform!r} devices; this "
                         "benchmark measures the chip and nothing else")
    if len(devs) < chips:
        raise SetupError(f"the cell needs {chips} chips, JAX found "
                         f"{len(devs)}")
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devs)}


def peak_memory() -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.devices())


def verdict(readings: dict, failed: int, limits: dict) -> bool:
    """``correct``: no answer failed, at least one was checked, and every
    number is within its limit."""
    return (failed == 0 and readings["answers_checked"] >= 1 and
            all(v <= limits.get(k, 0) for k, v in readings.items()
                if k != "answers_checked"))


def checks_line(readings: dict, limits: dict) -> dict:
    """Each number compared, beside its limit."""
    return {k: {"value": v, "limit": limits[k] if k in limits else
                (0 if k == "unanswered" else ">= 1")}
            for k, v in readings.items()}


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             t_start: Optional[float] = None) -> dict:
    """One run of ``cell``; returns the result line's object."""
    t_start = time.perf_counter() if t_start is None else t_start
    from repro.compat import make_mesh
    from repro.core.graph import Graph
    from repro.session import GraphSession

    device = device_info(cell.chips)
    try:
        pk = peaks_for(device["kind"])
    except UnknownDevice as e:
        raise SetupError(str(e)) from None
    config, traffic = cell.config, cell.traffic
    gen = config["generator"]
    graph = graph500.kronecker_edges(gen["scale"], gen["edge_factor"], seed)
    n, lo, hi, w, _ = graph
    src, dst, wt = graph500.both_directions(lo, hi, w)

    t_part = time.perf_counter()
    mesh = None
    if config.get("mesh_axis"):
        mesh = make_mesh((cell.chips,), (config["mesh_axis"],))
    sess = GraphSession.from_graph(
        Graph(n, src, dst, wt, directed=False), n_parts=config["n_parts"],
        partitioner=config["partitioner"], seed=config["partitioner_seed"],
        mesh=mesh)
    partition_s = time.perf_counter() - t_part
    del src, dst, wt
    resident = int(sess.pg.edges_per_part.sum())

    drv = Driver(traffic, config, graph, seed)
    warm = drv.run_steps(sess, traffic["warmup"])
    if any(q.error for q in warm):
        raise SetupError("a warm-up query failed")
    compile_s = sum(q.compile_s for q in warm)
    setup_s = time.perf_counter() - t_start

    misses0 = sess.stats.cache_misses
    tdir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    if trace:
        opts = jax.profiler.ProfileOptions()
        opts.host_tracer_level = 1
        opts.python_tracer_level = 0
        jax.profiler.start_trace(tdir, profiler_options=opts)
    queries = []
    w0 = time.perf_counter()
    try:
        while True:
            queries += drv.run_steps(sess, traffic["iteration"])
            if time.perf_counter() - w0 >= seconds:
                break
        window_s = time.perf_counter() - w0
    finally:
        if trace:
            jax.profiler.stop_trace()
    misses = sess.stats.cache_misses - misses0
    print(json.dumps({"window_runner_cache_misses": misses,
                      "queries": [[q.program, q.latency_s, q.supersteps]
                                  for q in queries]}), flush=True)
    device["memory_peak_bytes"] = peak_memory()

    tr = None
    if trace:
        files = glob.glob(os.path.join(tdir, "**", "*.xplane.pb"),
                          recursive=True)
        tr = Trace.from_file(files[0]) if files else None
        shutil.rmtree(tdir, ignore_errors=True)
    sess.close()
    del sess
    gc.collect()

    run = Run(cell, setup_s, partition_s, compile_s, window_s, queries,
              resident, misses, drv.batches, tr, pk)
    readings, failed = check(run, graph, seed)
    limits = config["limits"]
    correct = verdict(readings, failed, limits)

    metrics = {}
    for name, unit, read in (cell.per_layer if trace else cell.end_to_end):
        v = read(run)
        if v is not None:
            metrics[name] = {"value": float(v), "unit": unit}
    out = {"correct": bool(correct), "attempted": len(queries),
           "failed": int(failed), "metrics": metrics, "device": device}
    if trace and tr is not None and tr.ops:
        device["busy_s"] = tr.busy_s()
        device["window_s"] = tr.window_s()
        out["breakdown"] = {"device_ops": tr.top_ops(),
                            "idle_gaps": tr.idle_gaps()}
    out["checks"] = checks_line(readings, limits)
    return out


def main(argv=None, t_start: Optional[float] = None) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        cell = resolve(args.workload)
        configure_caches()
        out = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                       t_start=t_start)
    except SetupError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    print(json.dumps(out), flush=True)
    for k, v in out["checks"].items():
        print(f"check {k}: {v['value']} (limit {v['limit']})",
              file=sys.stderr)
    return 0
