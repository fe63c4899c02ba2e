"""Host spans on the profiler's clock.

A :class:`span` brackets one layer's or one op round's host work. It is a
``jax.profiler.TraceAnnotation``, so while a profiler runs (``jax.profiler.trace``)
it lands on the host planes of the same ``.xplane.pb`` as the device ops, on
one clock. It is timed with ``time.perf_counter`` as well, and its elapsed
microseconds (``span.us``) feed the fields the program reports whether or not a
profiler runs: ``SessionResult.stats_us``, the executor's ``phase_us`` and
``round_us``, and so on. The profiler is the only switch.

Names are ``<layer>.<step>`` (the catalogue is in docs/design/09-service.md,
"Tracing"). Spans sit at layer and op-round boundaries, never per row or per
work item, and nothing in one touches the device.

Request ids: ``span(..., requests=ids)`` names the requests a thread works on
for everything nested inside it on that thread; every span records the ids it
runs under as its ``requests`` argument (space-separated: the trace's argument
encoding ends a value at a comma).
"""

from __future__ import annotations

import itertools
import threading
import time
from typing import Iterable, Optional

from jax.profiler import TraceAnnotation

_request_ids = itertools.count(1)
_local = threading.local()


def new_request_id() -> int:
    """A process-wide id for one admitted request."""
    return next(_request_ids)


class span:
    """``with span(name, **args) as s: ...``; afterwards ``s.us`` is the elapsed
    host time in microseconds. ``s.set(**args)`` adds arguments known only at
    the end (bytes moved, rows made)."""

    __slots__ = ("name", "args", "requests", "us", "_outer", "_ann", "_t0")

    def __init__(self, name: str, requests: Optional[Iterable[int]] = None, **args):
        self.name = name
        self.args = args
        self.requests = None if requests is None else tuple(requests)
        self.us = 0.0

    def __enter__(self) -> "span":
        self._outer = getattr(_local, "requests", None)
        if self.requests is not None:
            _local.requests = self.requests
        self._ann = None
        if TraceAnnotation.is_enabled():
            ids = self._outer if self.requests is None else self.requests
            if ids:
                self.args["requests"] = " ".join(map(str, ids))
            self._ann = TraceAnnotation(self.name, **self.args)
            self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def set(self, **args) -> None:
        if self._ann is not None:
            self._ann.set_metadata(**args)

    def __exit__(self, *exc) -> bool:
        self.us = (time.perf_counter() - self._t0) * 1e6
        if self._ann is not None:
            self._ann.__exit__(*exc)
        _local.requests = self._outer
        return False
