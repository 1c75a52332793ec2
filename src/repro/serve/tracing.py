"""Phase spans and counters of the serving path.

One :class:`PhaseRecorder` belongs to an :class:`~repro.serve.AQPSession`
and is handed to every :class:`~repro.serve.LanePool` the session builds, so
its counters outlive pool rebuilds.  ``phase(name)`` is a context manager
that does two things each call:

* opens ``jax.profiler.TraceAnnotation("miss.<name>")``, which puts the span
  on the profiler's clock, the clock the device events share.  With no
  profiler running the annotation does nothing, so the spans are always on;
* adds the span's self time (its duration less that of the spans nested in
  it) and one call to ``stats()[name]``.

Spans sit at phase level only (a few per ``pump()``), never per lane or per
answer.  :meth:`PhaseRecorder.device_get` is the one blocking device->host
fetch of the pump path: it runs ``jax.device_get`` under the ``sync`` span
and counts it in :attr:`PhaseRecorder.syncs`.
"""
from __future__ import annotations

import time
from typing import Dict, List

import jax

SPAN_PREFIX = "miss."


class _Phase:
    __slots__ = ("rec", "name", "ann", "t0")

    def __init__(self, rec: "PhaseRecorder", name: str):
        self.rec, self.name = rec, name
        self.ann = jax.profiler.TraceAnnotation(SPAN_PREFIX + name)

    def __enter__(self) -> None:
        self.ann.__enter__()
        self.rec._child_ns.append(0)
        self.t0 = time.perf_counter_ns()

    def __exit__(self, *exc) -> None:
        dt = time.perf_counter_ns() - self.t0
        rec = self.rec
        child = rec._child_ns.pop()
        if rec._child_ns:
            rec._child_ns[-1] += dt
        acc = rec._acc.get(self.name)
        if acc is None:
            acc = rec._acc[self.name] = [0, 0]
        acc[0] += dt - child
        acc[1] += 1
        self.ann.__exit__(*exc)


class PhaseRecorder:
    """Per-session self time and call count of each serving phase."""

    def __init__(self):
        self._acc: Dict[str, List[int]] = {}   # name -> [self ns, calls]
        self._child_ns: List[int] = []         # open spans' child time
        self.syncs = 0                         # blocking device->host fetches

    def phase(self, name: str) -> _Phase:
        """Context manager: one span of phase ``name``."""
        return _Phase(self, name)

    def device_get(self, x):
        """``jax.device_get(x)`` under the ``sync`` span, counted."""
        with self.phase("sync"):
            out = jax.device_get(x)
        self.syncs += 1
        return out

    def stats(self) -> Dict[str, Dict[str, float]]:
        """``{name: {"s": self_seconds, "calls": n}}`` since construction."""
        return {name: {"s": ns * 1e-9, "calls": calls}
                for name, (ns, calls) in self._acc.items()}
