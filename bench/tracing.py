"""Reduce a profiler trace (``.xplane.pb``) to device busy time, idle gaps
and the benchmark's host spans, on one clock.

Device activity is read from the ``XLA Ops`` line of every ``/device:TPU:N``
plane: an operation's interval is the time it ran on that chip. Busy time is
the union of those intervals. Host spans are the ``TraceAnnotation`` events
the benchmark itself records, named ``bench/<step>`` (``bench/query``,
``bench/update``, ``bench/flush``, ``bench/result_copy``), found on any host
plane. The window is the stretch from the first span's start to the last
span's end.

An idle gap is a stretch of the window in which a chip ran no operation; it
is named by the host span that overlaps it most (``no_span`` when none does).
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import re

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench/"
NS = 1e-9
MIN_GAP_NS = 1000     # shorter gaps are the clock's rounding between ops


def _merge(iv: np.ndarray) -> np.ndarray:
    """Union of ``[start, end)`` intervals ([k, 2] ns), sorted, disjoint."""
    if iv.size == 0:
        return iv.reshape(0, 2)
    iv = iv[np.argsort(iv[:, 0], kind="stable")]
    ends = np.maximum.accumulate(iv[:, 1])
    new = np.ones(len(iv), bool)
    new[1:] = iv[1:, 0] > ends[:-1]
    starts = iv[new, 0]
    idx = np.flatnonzero(new)
    last = np.r_[idx[1:] - 1, len(iv) - 1]
    return np.stack([starts, ends[last]], 1)


def _overlap(merged: np.ndarray, a: float, b: float) -> float:
    """ns of ``[a, b)`` covered by the disjoint intervals ``merged``."""
    if merged.size == 0 or b <= a:
        return 0.0
    lo = np.clip(merged[:, 0], a, b)
    hi = np.clip(merged[:, 1], a, b)
    return float(np.sum(hi - lo))


_OPCODE = re.compile(r"[\s})]([a-z][a-z0-9_-]*)\(")


def op_name(event_name: str) -> str:
    """``%fusion.3 fusion f32[16,2097152]`` from an HLO instruction's text
    (``%fusion.3 = f32[16,2097152]{1,0} fusion(...), ...``)."""
    lhs, _, rest = event_name.partition(" = ")
    m = _OPCODE.search(" " + rest)
    if not m:
        return lhs[:80]
    shape = rest[:m.start()].split("{")[0].strip(" (")
    return f"{lhs} {m.group(1)} {shape}"[:80]


def _nested_time(events: list) -> list:
    """For each (start, end, name) event, the ns covered by the events
    directly nested in it (the ops line nests a loop's body in the loop)."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][0], -events[i][1]))
    inner = [0.0] * len(events)
    stack: list = []
    for i in order:
        s, e, _ = events[i]
        while stack and events[stack[-1]][1] <= s:
            stack.pop()
        if stack:
            inner[stack[-1]] += e - s
        stack.append(i)
    return inner


@dataclasses.dataclass
class Trace:
    """Device operations per chip and the benchmark's host spans."""
    ops: dict            # chip id -> list of (start_ns, end_ns, name)
    spans: list          # (start_ns, end_ns, name) of bench/ annotations

    @classmethod
    def from_profile(cls, profile) -> "Trace":
        ops = collections.defaultdict(list)
        spans = []
        for plane in profile.planes:
            m = DEVICE_PLANE.match(plane.name)
            for line in plane.lines:
                if m is not None and line.name == OPS_LINE:
                    ops[int(m.group(1))] += [
                        (e.start_ns, e.start_ns + e.duration_ns, e.name)
                        for e in line.events]
                elif m is None:
                    spans += [(e.start_ns, e.start_ns + e.duration_ns,
                               e.name[len(SPAN_PREFIX):])
                              for e in line.events
                              if e.name.startswith(SPAN_PREFIX)]
        spans.sort()
        return cls(dict(ops), spans)

    @classmethod
    def from_file(cls, path: str) -> "Trace":
        from jax.profiler import ProfileData
        return cls.from_profile(ProfileData.from_file(path))

    # ------------------------------------------------------------------ #
    @functools.cached_property
    def _busy(self) -> dict:
        """chip id -> the union of its operations' intervals."""
        return {c: _merge(np.array([(a, b) for a, b, _ in ev], float)
                          .reshape(-1, 2))
                for c, ev in self.ops.items()}

    @property
    def n_chips(self) -> int:
        return len(self.ops)

    @property
    def window(self) -> tuple:
        """(start_ns, end_ns) from the first span's start to the last
        span's end."""
        return (min(s for s, _, _ in self.spans),
                max(e for _, e, _ in self.spans))

    def busy_s(self, a: float = None, b: float = None) -> float:
        """Seconds in ``[a, b)`` (default: the window) in which a chip ran
        an operation, averaged over the chips."""
        if a is None:
            a, b = self.window
        busy = self._busy
        return sum(_overlap(m, a, b) for m in busy.values()) * NS / \
            max(len(busy), 1)

    def window_s(self) -> float:
        a, b = self.window
        return (b - a) * NS

    def named(self, name: str) -> list:
        """(start_ns, end_ns) of every span called ``bench/<name>``."""
        return [(s, e) for s, e, n in self.spans if n == name]

    def top_ops(self, k: int = 10) -> list:
        """[name, seconds] of the ``k`` operations with the most self time
        in the window (time not covered by the operations nested in them,
        such as a while loop's body), summed over their runs and averaged
        over the chips."""
        a, b = self.window
        tot = collections.Counter()
        for ev in self.ops.values():
            for (s, e, name), inner in zip(ev, _nested_time(ev)):
                tot[op_name(name)] += max(0.0, min(e, b) - max(s, a)) - inner
        n = max(len(self.ops), 1)
        return [[name, t * NS / n] for name, t in tot.most_common(k)]

    def idle_gaps(self, k: int = 10) -> list:
        """[span name, seconds] of the ``k`` longest stretches of the window
        in which the lowest-numbered chip ran no operation."""
        if not self.ops:
            return []
        a, b = self.window
        busy = self._busy[min(self.ops)]
        edges = np.r_[a, np.clip(busy.ravel(), a, b), b].reshape(-1, 2)
        gaps = [(s, e) for s, e in edges if e - s >= MIN_GAP_NS]
        gaps.sort(key=lambda g: g[0] - g[1])
        out = []
        for s, e in gaps[:k]:
            best, name = 0.0, "no_span"
            for ss, se, sn in self.spans:
                ov = min(e, se) - max(s, ss)
                if ov > best:
                    best, name = ov, sn
            out.append([name, float(e - s) * NS])
        return out
