"""Drive one whole run of a cell on the CPU at a small scale: what the tests
of the mixes and of the faults share. The harness's look for a chip and its
peaks table are patched out, and the configuration's scale is set to
``SCALE``; the rest of the run is the measurement path as it stands.
``python -m bench.tests.rehearse <workload> <seed> [fault]`` runs one in a
process of its own (the four-device cell needs
``XLA_FLAGS=--xla_force_host_platform_device_count=4`` set before JAX
starts) and prints its result line."""
import json
import os
import sys

import jax
import numpy as np

from bench import harness

SCALE = 10
#: the four-chip cell, kept here until BENCHMARK.json lists it (PERF.md,
#: Open questions): the harness's shard_map path, which a later PR adds the
#: cell to as data alone, is rehearsed all the same
X4 = "g500-s20-x4.traverse"
X4_CONFIG = {"name": "graph500-s20-x4",
             "file": "bench/configs/graph500-s20-x4.json"}


def cell(workload: str) -> harness.Cell:
    """The cell as ``harness.resolve`` finds it, at ``SCALE``."""
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    if workload == X4 and X4 not in [w["name"] for w in spec["workloads"]]:
        spec["configs"].append(X4_CONFIG)
        spec["workloads"].append({"name": X4, "config": X4_CONFIG["name"],
                                  "traffic": "traverse", "chips": 4})
    c = harness.resolve(workload, spec)
    c.config["generator"]["scale"] = SCALE
    return c


def cpu_device_info(chips: int) -> dict:
    devs = jax.devices()
    assert len(devs) >= chips, (chips, devs)
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def rehearse(mp, workload: str, seed: int, seconds: float = 0.3) -> dict:
    """One run on the CPU; ``mp`` patches (pytest's ``monkeypatch``)."""
    mp.setattr(harness, "device_info", cpu_device_info)
    mp.setattr(harness, "peaks_for", lambda kind: None)
    return harness.run_cell(cell(workload), seed, seconds, False)


# --------------------------------------------------------------------------- #
# faults planted under the timed path
# --------------------------------------------------------------------------- #
def _wrap_query(mp, change):
    from repro.session import GraphSession
    real = GraphSession.query

    def query(self, program, params=None, **kw):
        res, st = real(self, program, params, **kw)
        return change(self, program, params, np.array(res)), st

    mp.setattr(GraphSession, "query", query)


def state_unchanged(mp):
    """Every query returns its program's initial state."""
    def initial(sess, program, params, res):
        pg = sess.pg
        if params is None:                       # CC: own labels
            return np.where(pg.vmask, pg.gvid, 2**31 - 1).astype(res.dtype)
        return np.where(pg.gvid == params["source"], 0.0,
                        np.inf).astype(res.dtype)
    _wrap_query(mp, initial)


def answer_altered(mp):
    """One held vertex's answer is off by one where it is produced."""
    def alter(sess, program, params, res):
        pg = sess.pg
        live = np.argwhere(pg.vmask & pg.is_master & np.isfinite(res)
                           & (res > 0))
        if len(live):
            res[tuple(live[0])] += 1
        return res
    _wrap_query(mp, alter)


def exchange_left_out(mp):
    """The SBS exchange between partitions (chips) combines nothing: each
    partition keeps its own boundary values."""
    from repro.core import sbs
    mp.setattr(sbs.SimExchange, "all_combine",
               lambda self, bufs, combiner: bufs[0])
    mp.setattr(sbs.ShardExchange, "all_combine",
               lambda self, buf, combiner: buf)


def flush_unchanged(mp):
    """A flush applies nothing: the graph state stays as it was."""
    from repro import session
    mp.setattr(session._SessionBuffer, "flush",
               lambda self, _auto=False: None)


def half_batch(mp):
    """Half of every insert batch is left out."""
    from repro.session import GraphSession
    real = GraphSession.update

    def update(self, adds=None, deletes=None):
        s, d, w = adds
        m = s.size // 2
        keep = np.r_[0:m // 2, m:m + m // 2]
        return real(self, adds=(s[keep], d[keep], w[keep]), deletes=deletes)

    mp.setattr(GraphSession, "update", update)


FAULTS = {f.__name__: f for f in (state_unchanged, answer_altered,
                                  exchange_left_out, flush_unchanged,
                                  half_batch)}


class _Patch:
    """The bit of pytest's monkeypatch a subprocess needs."""

    def setattr(self, obj, name, value):
        setattr(obj, name, value)


if __name__ == "__main__":
    workload, seed = sys.argv[1], int(sys.argv[2])
    if len(sys.argv) > 3:
        FAULTS[sys.argv[3]](_Patch())
    print(json.dumps(rehearse(_Patch(), workload, seed)))
