"""Named host spans and device scopes, for reading a profile of a session.

``span(name)`` is a host span ``drone/<name>``: a
``jax.profiler.TraceAnnotation``, so under ``jax.profiler.trace`` it lands
on the host plane of the same ``.xplane.pb`` as the device operations, on
the same clock. Outside a profile it costs about a microsecond and records
nothing. Spans nest lexically; a reader finds a span's parent by interval
containment on its thread. ``span(...).set_metadata(key=value)`` attaches a
number to the event (``session/query`` carries the bytes it uploaded).

``scope(name)`` is a device scope ``drone_<name>``: a ``jax.named_scope``
around traced code. It only adds the name to the ``op_name`` metadata of
the operations traced inside it (the compiled program is otherwise the
same), and a TPU profile reports that name stack with each operation.

The names in use, and what each bounds, are listed in docs/SERVING.md
("Tracing a session").
"""
from __future__ import annotations

import jax

__all__ = ["SPAN_PREFIX", "SCOPE_PREFIX", "span", "scope"]

SPAN_PREFIX = "drone/"
SCOPE_PREFIX = "drone_"


def span(name: str) -> jax.profiler.TraceAnnotation:
    """A host span ``drone/<name>`` around the code in its ``with``."""
    return jax.profiler.TraceAnnotation(SPAN_PREFIX + name)


def scope(name: str):
    """A device scope ``drone_<name>`` on the operations traced inside."""
    return jax.named_scope(SCOPE_PREFIX + name)
