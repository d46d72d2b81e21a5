"""Host spans: where one ``serve()`` call's host time goes.

A span times a block of host code on two clocks at once.  It opens a
``jax.profiler.TraceAnnotation``, so under a profiler the block lands on
the host plane beside the device operations it dispatched or waited for
(with no profiler active that costs a check), and it adds its
``time.perf_counter`` duration to per-name totals held by one
:class:`Spans` object.  ``serve()`` keeps one per call and returns the
totals in its report as ``out["spans"]``::

    {"serve.step": {"count": 127, "total_s": 7.12, "max_s": 0.118}, ...}

A nested span counts its whole duration in its own total, so a parent's
total includes its children's.  A span an exception leaves open adds
nothing; its profiler event ends when the annotation is collected.
"""
from __future__ import annotations

import time
from typing import Dict, List

import jax


class Span:
    """One timed block: a context manager, or ``open()`` / ``close()``
    where a ``with`` would have to wrap a whole loop body."""

    __slots__ = ("_totals", "_name", "_ann", "_t")

    def __init__(self, totals: Dict[str, List[float]], name: str, ann):
        self._totals, self._name, self._ann = totals, name, ann

    def open(self) -> "Span":
        self._ann.__enter__()
        self._t = time.perf_counter()
        return self

    def close(self) -> None:
        dt = time.perf_counter() - self._t
        self._ann.__exit__(None, None, None)
        tot = self._totals.setdefault(self._name, [0, 0.0, 0.0])
        tot[0] += 1
        tot[1] += dt
        tot[2] = max(tot[2], dt)

    def __enter__(self) -> "Span":
        return self.open()

    def __exit__(self, *exc) -> None:
        self.close()


class Spans:
    """Per-name span totals of one ``serve()`` call."""

    def __init__(self):
        self._totals: Dict[str, List[float]] = {}

    def __call__(self, name: str, **meta) -> Span:
        """A span named ``name``; ``meta`` (e.g. ``request=3``) goes on
        the profiler event only."""
        return Span(self._totals, name,
                    jax.profiler.TraceAnnotation(name, **meta))

    def step(self, name: str, step_num: int) -> Span:
        """A span the profiler marks as one step of a loop."""
        return Span(self._totals, name,
                    jax.profiler.StepTraceAnnotation(name,
                                                     step_num=step_num))

    def report(self) -> Dict[str, Dict[str, float]]:
        return {k: {"count": int(n), "total_s": s, "max_s": m}
                for k, (n, s, m) in self._totals.items()}
