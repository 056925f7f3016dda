"""Program spans: named host intervals in the ``jax.profiler`` trace.

``span(name, **ids)`` opens ``repro.<name>`` as a
``jax.profiler.TraceAnnotation`` carrying ``ids`` as its metadata, so the
span lands in the same trace as the device's programs and on the same clock:
an interval in which the chip ran nothing can be charged to the host work
that was open at the time.  Ids that are ``None`` are left out, and an id
known only inside the span is added with ``set_metadata``.

With no trace being recorded a span is a shared do-nothing context and no
metadata is built, so the spans cost one check each on the serving path.

The spans, from the serving pump down:

* ``repro.pump`` -- one ``ServingSession.step`` / ``flush`` (``flush``);
* ``repro.admit`` -- the policy's admission and the waits it records
  (``admitted``);
* ``repro.plan`` -- ``engine.plan_groups`` (``requests``, ``groups``);
  ``plan_seconds`` times the same interval;
* ``repro.order`` -- group sequencing and per-plan order re-solving
  (``groups``);
* ``repro.group`` -- one executed group, attempts through resolution
  (``group``, ``valid``, ``rows``, ``attempt``);
* ``repro.dispatch`` -- one task's run in the executor: residency walk,
  parameter gather, the compiled call, activation caching (``group``,
  ``task``, ``resume``, ``rows``);
* ``repro.predict`` -- the cost-model accounting after a group ran
  (``group``);
* ``repro.resolve`` -- the counter merges and the futures' resolution
  (``group``, ``requests``).
"""
from __future__ import annotations

from typing import Any

import jax

PREFIX = "repro."
_recording = jax.profiler.TraceAnnotation.is_enabled


class _Off:
    """A span while no trace is recorded."""

    __slots__ = ()

    def __enter__(self) -> "_Off":
        return self

    def __exit__(self, *exc: Any) -> bool:
        return False

    def set_metadata(self, **ids: Any) -> None:
        pass


_OFF = _Off()


def span(name: str, **ids: Any):
    """A context manager recording ``repro.<name>`` with ``ids`` into the
    profiler's trace while one is being recorded, and nothing otherwise."""
    if not _recording():
        return _OFF
    return jax.profiler.TraceAnnotation(
        PREFIX + name, **{k: v for k, v in ids.items() if v is not None})
