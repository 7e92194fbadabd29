"""The program's own spans and device scopes in the benchmark's trace.

``repro.obs`` names host spans ``drone/<layer>/<step>`` (TraceAnnotations,
on the same clock as the device operations) and device scopes
``drone_<phase>`` (``jax.named_scope``s, which land in the ``op_name``
metadata of every HLO instruction traced inside them).
``bench/tracing.py``'s ``Trace`` keeps only the benchmark's ``bench/``
spans and each operation's HLO text. The readers of the program's metrics
are loaded before the run and import this module; importing it makes
``Trace.from_file`` keep two lists more, and changes nothing that ``Trace``
kept already:

- ``program_spans``: (start_ns, end_ns, name, thread, stats) of every
  ``drone/`` event on a host plane, the prefix taken off the name;
- ``scoped_ops``: chip id -> (start_ns, end_ns, scope) of every operation
  of its ``XLA Ops`` line, ``scope`` the innermost ``drone_<phase>`` of the
  operation's name stack, or None.

An operation's scope is read from the first of: a stat of its event or of
its event metadata that holds a name stack (a v5e profile keeps it as the
metadata's ``tf_op``), or else its instruction in the HLO module that the
profile carries (plane ``/host:metadata``, one ``Hlo Proto`` per module;
the module of an operation is the ``XLA Modules`` event it runs in): the
instruction's ``op_name``, or the one scope of the instructions it calls.
The second source names the loops and the scatter fusions that the TPU
compiler builds after tracing, which have no ``tf_op``.
``jax.profiler.ProfileData`` shows event stats only, so the metadata is
read from the serialized profile with the few lines of protobuf wire
format below.

A program without those spans and scopes leaves both lists empty, and the
readers then read nothing. The functions at the end do the arithmetic:
seconds of a span, the chip-idle seconds inside it, its children's
seconds, and the device self time of a scope.
"""
from __future__ import annotations

import bisect
import collections
import re

import numpy as np

from bench.tracing import DEVICE_PLANE, NS, OPS_LINE, Trace

SPAN_PREFIX = "drone/"
SCOPE = re.compile(r"drone_([a-z]+)")
MODULES_LINE = "XLA Modules"
METADATA_PLANE = "/host:metadata"
HLO_STAT = "Hlo Proto"


def _innermost(text):
    """The last ``drone_<phase>`` named in ``text``, or None."""
    found = SCOPE.findall(text) if isinstance(text, str) else []
    return found[-1] if found else None


# --------------------------------------------------------------------------- #
# protobuf wire format: just enough of XSpace and HloProto
# --------------------------------------------------------------------------- #
def _varint(b, i: int) -> tuple:
    x = shift = 0
    while True:
        c = b[i]
        i += 1
        x |= (c & 0x7F) << shift
        if c < 0x80:
            return x, i
        shift += 7


def _fields(b, span=None):
    """(field number, value) of one message in ``b[span]``: an int for a
    varint, a (start, end) pair into ``b`` for anything else."""
    i, end = span or (0, len(b))
    while i < end:
        key, i = _varint(b, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 2:
            n, i = _varint(b, i)
            v, i = (i, i + n), i + n
        elif wire in (1, 5):
            n = 8 if wire == 1 else 4
            v, i = (i, i + n), i + n
        else:
            raise ValueError(f"protobuf wire type {wire}")
        yield key >> 3, v


def _str(b, span) -> str:
    return bytes(b[span[0]:span[1]]).decode("utf-8", "replace")


def _event_metadata(b, plane) -> dict:
    """XPlane ``plane`` -> {event metadata name: {stat name: str or bytes}}
    (XPlane: name 2, event_metadata 4, stat_metadata 5; XEventMetadata:
    name 2, stats 5; XStat: metadata_id 1, str_value 5, bytes_value 6)."""
    stat_names, metas = {}, []
    for f, v in _fields(b, plane):
        if f == 5:
            entry = dict(_fields(b, v))
            md = dict(_fields(b, entry[2])) if 2 in entry else {}
            stat_names[entry.get(1)] = _str(b, md[2]) if 2 in md else None
        elif f == 4:
            entry = dict(_fields(b, v))
            if 2 in entry:
                metas.append(entry[2])
    out = {}
    for span in metas:
        name, stats = None, {}
        for f, v in _fields(b, span):
            if f == 2:
                name = _str(b, v)
            elif f == 5:
                st = dict(_fields(b, v))
                if 5 in st:
                    stats[stat_names.get(st.get(1))] = _str(b, st[5])
                elif 6 in st:
                    stats[stat_names.get(st.get(1))] = bytes(
                        b[st[6][0]:st[6][1]])
        out[name] = stats
    return out


def _ids(b, v) -> list:
    """A repeated int64 field's value: one varint, or a packed run."""
    if isinstance(v, int):
        return [v]
    out, i = [], v[0]
    while i < v[1]:
        x, i = _varint(b, i)
        out.append(x)
    return out


def _hlo_scopes(hlo: bytes) -> dict:
    """{instruction name: innermost ``drone_`` scope or None} of a
    serialized HloProto (hlo_module 1; HloModuleProto computations 3;
    HloComputationProto instructions 2, id 5; HloInstructionProto name 1,
    metadata 7, called_computation_ids 38; OpMetadata op_name 2). An
    instruction whose own op_name names no scope, such as a fusion the
    compiler made after tracing, takes the scope of the instructions it
    calls where they all name one and the same."""
    b = memoryview(hlo)
    comps = {}                     # id -> [(name, scope, called ids)]
    for f, mod in _fields(b):
        if f != 1:
            continue
        for cf, comp in _fields(b, mod):
            if cf != 3:
                continue
            cid, instrs = None, []
            for inf, v in _fields(b, comp):
                if inf == 5:
                    cid = v
                elif inf == 2:
                    name, scope, calls = None, None, []
                    for xf, xv in _fields(b, v):
                        if xf == 1:
                            name = _str(b, xv)
                        elif xf == 7:
                            scope = next((_innermost(_str(b, ov))
                                          for of, ov in _fields(b, xv)
                                          if of == 2), None)
                        elif xf == 38:
                            calls += _ids(b, xv)
                    instrs.append((name, scope, calls))
            comps[cid] = instrs

    memo = {}

    def called(ids) -> set:
        found = set()
        for c in ids:
            if c not in memo:
                memo[c] = set()
                for _, scope, calls in comps.get(c, ()):
                    memo[c] |= {scope} if scope else called(calls)
            found |= memo[c]
        return found

    out = {}
    for instrs in comps.values():
        for name, scope, calls in instrs:
            if scope is None:
                found = called(calls)
                scope = found.pop() if len(found) == 1 else None
            out[name] = scope
    return out


def _profile_metadata(raw: bytes) -> tuple:
    """({device plane name: its event metadata}, {module event name:
    {instruction: scope}}) of a serialized XSpace (planes: field 1)."""
    b = memoryview(raw)
    device, modules = {}, {}
    for f, plane in _fields(b):
        if f != 1:
            continue
        name = next((_str(b, v) for pf, v in _fields(b, plane) if pf == 2),
                    "")
        if DEVICE_PLANE.match(name):
            device[name] = _event_metadata(b, plane)
        elif name == METADATA_PLANE:
            for mod, stats in _event_metadata(b, plane).items():
                if isinstance(stats.get(HLO_STAT), bytes):
                    modules[mod] = _hlo_scopes(stats[HLO_STAT])
    return device, modules


# --------------------------------------------------------------------------- #
# the extension of Trace
# --------------------------------------------------------------------------- #
def _instruction(event_name: str) -> str:
    """``fusion.3`` from ``%fusion.3 = f32[...] fusion(...)``."""
    return event_name.partition(" = ")[0].strip().lstrip("%")


def collect(trace: Trace, profile, raw: bytes = b"") -> Trace:
    """Add ``program_spans`` and ``scoped_ops`` from ``profile`` (and from
    ``raw``, the same profile serialized, for the metadata)."""
    device_md, modules = _profile_metadata(raw) if raw else ({}, {})
    spans, scoped = [], collections.defaultdict(list)
    for plane in profile.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m is None:
            spans += [(e.start_ns, e.start_ns + e.duration_ns,
                       e.name[len(SPAN_PREFIX):], (plane.name, line.name),
                       dict(e.stats))
                      for line in plane.lines for e in line.events
                      if e.name.startswith(SPAN_PREFIX)]
            continue
        md = device_md.get(plane.name, {})
        runs = sorted((e.start_ns, e.start_ns + e.duration_ns, e.name)
                      for line in plane.lines if line.name == MODULES_LINE
                      for e in line.events)
        starts = [r[0] for r in runs]
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            for e in line.events:
                stats = list(e.stats) + list(md.get(e.name, {}).items())
                scope = next((_innermost(v) for _, v in stats
                              if isinstance(v, str) and "drone_" in v), None)
                if scope is None and modules:
                    k = bisect.bisect_right(starts, e.start_ns) - 1
                    names = modules.get(runs[k][2], {}) if k >= 0 else {}
                    scope = names.get(_instruction(e.name))
                scoped[int(m.group(1))].append(
                    (e.start_ns, e.start_ns + e.duration_ns, scope))
    spans.sort(key=lambda s: s[:3])
    trace.program_spans = spans
    trace.scoped_ops = dict(scoped)
    return trace


def from_serialized(raw: bytes) -> Trace:
    """A ``Trace`` of a serialized XSpace, with the program's spans and
    scopes."""
    from jax.profiler import ProfileData
    profile = ProfileData.from_serialized_xspace(raw)
    return collect(Trace.from_profile(profile), profile, raw)


def _from_file(cls, path: str) -> Trace:
    with open(path, "rb") as f:
        return from_serialized(f.read())


# The harness builds its Trace with from_file and deletes the profile before
# any reader runs, so this is where the program's names can be kept.
if not hasattr(Trace, "program_spans"):
    Trace.from_file = classmethod(_from_file)
    Trace.program_spans = ()
    Trace.scoped_ops = {}


# --------------------------------------------------------------------------- #
# spans
# --------------------------------------------------------------------------- #
def named(trace: Trace, name: str) -> list:
    """(start_ns, end_ns, thread, stats) of every ``drone/<name>`` span
    inside the trace's window."""
    if not trace.spans:
        return []
    a, b = trace.window
    return [(s, e, t, st) for s, e, n, t, st in trace.program_spans
            if n == name and a <= s and e <= b]


def span_s(trace: Trace, name: str) -> float:
    """Summed seconds of the ``drone/<name>`` spans in the window."""
    return sum(e - s for s, e, _, _ in named(trace, name)) * NS


def idle_in(trace: Trace, name: str) -> float:
    """Seconds inside the ``drone/<name>`` spans in which no chip ran an
    operation (averaged over the chips, as ``busy_s`` is)."""
    return sum((e - s) * NS - trace.busy_s(s, e)
               for s, e, _, _ in named(trace, name))


def children_s(trace: Trace, parent: str, names) -> list:
    """For each ``drone/<parent>`` span in the window, the summed seconds of
    the spans called one of ``names`` that lie inside it on its thread."""
    kids = [k for n in names for k in named(trace, n)]
    return [sum(ke - ks for ks, ke, kt, _ in kids
                if kt == t and s <= ks and ke <= e) * NS
            for s, e, t, _ in named(trace, parent)]


def mean_per(trace: Trace, name: str, per: str):
    """Seconds of ``drone/<name>`` per ``drone/<per>`` span in the window,
    or None where there is no ``per`` span."""
    n = len(named(trace, per))
    return span_s(trace, name) / n if n else None


def stat_per(trace: Trace, stat: str, name: str):
    """Mean of the number ``stat`` over the ``drone/<name>`` spans in the
    window that carry it, or None where none does."""
    got = [st[stat] for _, _, _, st in named(trace, name) if stat in st]
    return float(np.mean(got)) if got else None


# --------------------------------------------------------------------------- #
# device scopes
# --------------------------------------------------------------------------- #
def has_scopes(trace: Trace) -> bool:
    return any(sc is not None for ev in trace.scoped_ops.values()
               for _, _, sc in ev)


def _segments(ev: list) -> tuple:
    """(starts, ends, scopes) of the stretches in which each operation of
    one chip is the innermost one running: its self time, the time that
    the operations nested in it (a loop's body in the loop) leave it."""
    order = sorted(range(len(ev)), key=lambda i: (ev[i][0], -ev[i][1]))
    out, stack, cursor = [], [], 0.0

    def close_until(t):
        nonlocal cursor
        while stack and ev[stack[-1]][1] <= t:
            j = stack.pop()
            out.append((cursor, ev[j][1], ev[j][2]))
            cursor = max(cursor, ev[j][1])

    for i in order:
        s = ev[i][0]
        close_until(s)
        if stack:
            out.append((cursor, s, ev[stack[-1]][2]))
        cursor = s
        stack.append(i)
    close_until(float("inf"))
    out = [seg for seg in out if seg[1] > seg[0]]
    return (np.array([x[0] for x in out], float),
            np.array([x[1] for x in out], float),
            np.array([x[2] for x in out], object))


def scope_self_s(trace: Trace, scope, a: float, b: float) -> float:
    """Self time, in seconds averaged over the chips, in ``[a, b)`` of the
    operations whose innermost scope is ``drone_<scope>``; ``scope`` None
    counts every operation. On a whole window this is the self time that
    ``Trace.top_ops`` sums per operation."""
    segs = trace.__dict__.get("_scope_segments")
    if segs is None:
        segs = trace._scope_segments = [_segments(ev) for ev in
                                        trace.scoped_ops.values()]
    tot = 0.0
    for starts, ends, scopes in segs:
        ov = np.clip(ends, a, b) - np.clip(starts, a, b)
        if scope is not None:
            ov = ov[scopes == scope]
        tot += float(ov.sum())
    return tot * NS / max(len(segs), 1)


def scope_share(trace: Trace, scope: str, spans) -> float:
    """Percent of the device self time inside ``spans`` ((start_ns, end_ns)
    pairs) that falls under ``drone_<scope>``; None where the trace has no
    scoped operation or no device time there."""
    if not has_scopes(trace) or not spans:
        return None
    part = sum(scope_self_s(trace, scope, a, b) for a, b in spans)
    whole = sum(scope_self_s(trace, None, a, b) for a, b in spans)
    return 100.0 * part / whole if whole > 0 else None
