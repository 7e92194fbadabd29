"""Runtime retrace sanitizer: assert a region performed no hidden compiles.

The static rules (DL001-DL005) catch retrace *hazards*; this module catches
the retrace itself. :func:`retrace_guard` wraps any region — a query loop, a
serving benchmark, a test body — and raises :class:`RetraceError` (or warns,
configurable) when jax's tracing counter shows a compile the region did not
account for. It replaces the trace-counter boilerplate that used to be
copy-pasted across ``test_session.py``/``test_edge_backends.py``/
``test_serving.py``:

    with retrace_guard():                 # was: jtu.count_jit_tracing_...
        for q in queries:
            session.query(prog, q)        # any retrace -> RetraceError

Sessions whose compiles are *expected* (cold-start runner builds) are passed
in so their ``stats.cache_misses`` deltas excuse the traces they cause:

    with retrace_guard(session, pool):    # cold compiles allowed,
        ...                               # anything else raises

Production use: ``GraphSession(debug_sanitize=True)`` arms the guard around
every cache-hit launch — an AOT-compiled runner re-entering the tracer is
always a bug — and ``debug_sanitize="warn"`` downgrades it to a warning.

The counter is ``jax._src.test_util.count_jit_tracing_cache_miss`` (private
but stable across the pinned jax line): a context manager that yields a
callable returning the misses counted since entry.
"""
from __future__ import annotations

import contextlib
import dataclasses
import warnings
from typing import Iterator

__all__ = ["RetraceError", "RetraceWarning", "RetraceGuard",
           "retrace_guard"]


class RetraceError(RuntimeError):
    """An unexpected jax trace/compile happened inside a guarded region."""


class RetraceWarning(UserWarning):
    """Warning twin of :class:`RetraceError` (``action="warn"``)."""


@dataclasses.dataclass
class RetraceGuard:
    """What the guarded region did; populated when the ``with`` exits.

    traces             jit tracing-cache misses observed
    expected_compiles  runner compiles the passed sessions recorded —
                       these excuse their traces
    allow              extra traces tolerated (constructor arg)
    triggered          the guard found unexpected traces (after the region
                       raised or warned, for ``action="warn"`` callers)
    """

    traces: int = 0
    expected_compiles: int = 0
    allow: int = 0
    triggered: bool = False

    @property
    def unexpected(self) -> int:
        if self.expected_compiles > 0:
            return 0
        return max(0, self.traces - self.allow)


@contextlib.contextmanager
def retrace_guard(*sessions, allow: int = 0, action: str = "raise",
                  label: str = "") -> Iterator[RetraceGuard]:
    """Fail if the region traced more than its sessions' compiles explain.

    sessions   objects with ``stats.cache_misses`` (``GraphSession``,
               ``SessionPool`` members, ...). Compiles they record inside
               the region are expected — a cold start may trace several
               internal jits, so any recorded compile disarms the count
               check for that region.
    allow      tolerated traces when no session compile occurred (for
               regions that intentionally build one ad-hoc jit).
    action     ``"raise"`` -> :class:`RetraceError`,
               ``"warn"`` -> :class:`RetraceWarning`.
    label      prefix for the error message (e.g. the query being served).
    """
    if action not in ("raise", "warn"):
        raise ValueError(f"retrace_guard action must be 'raise' or 'warn', "
                         f"got {action!r}")
    guard = RetraceGuard(allow=allow)
    before = [s.stats.cache_misses for s in sessions]
    import jax._src.test_util as jtu
    with jtu.count_jit_tracing_cache_miss() as traced:
        yield guard
    guard.traces = int(traced())
    guard.expected_compiles = sum(
        s.stats.cache_misses - b for s, b in zip(sessions, before))
    if guard.unexpected:
        guard.triggered = True
        where = f"{label}: " if label else ""
        msg = (f"{where}{guard.traces} unexpected jax trace(s) in a "
               f"retrace_guard region (expected_compiles="
               f"{guard.expected_compiles}, allow={guard.allow}). A "
               f"compiled runner re-entered the tracer — check for "
               f"closure-captured arrays (DL001), unstable cache keys "
               f"(DL002), or shape/dtype drift in the inputs.")
        if action == "raise":
            raise RetraceError(msg)
        warnings.warn(msg, RetraceWarning, stacklevel=3)
