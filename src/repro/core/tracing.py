"""The solver's one timing mechanism: host spans and device scopes named
``hylu.<name>``, on the clock of ``jax.profiler``.

* :func:`span` marks a host interval.  It opens a
  ``jax.profiler.TraceAnnotation`` (free when no profiler trace is being
  captured), and with ``into`` also adds the interval's wall seconds to
  ``into[key or name]``: the ``timings``/``stats`` dicts the solver reports
  are filled by the same spans a trace shows.
* :func:`scope` names the device operations traced inside it: XLA keeps the
  name in each operation's ``op_name`` metadata, so a device trace can
  charge every operation to its phase.  It changes metadata only, never
  the computation.
"""
from __future__ import annotations

import contextlib
import time

import jax

PREFIX = "hylu."


@contextlib.contextmanager
def span(name: str, into: dict | None = None, key: str | None = None):
    """Host span ``hylu.<name>``; adds its seconds to ``into[key or name]``
    when it ends without raising."""
    with jax.profiler.TraceAnnotation(PREFIX + name):
        t0 = time.perf_counter()
        yield
        if into is not None:
            k = key or name
            into[k] = into.get(k, 0.0) + time.perf_counter() - t0


def scope(name: str):
    """Device scope ``hylu.<name>`` (``jax.named_scope``)."""
    return jax.named_scope(PREFIX + name)
