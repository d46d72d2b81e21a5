"""The serving loop's own numbers: its host spans in a traced run, and
the span totals and counters ``serve()`` reports for each job.

``serve()`` wraps each layer of its loop in a host span named
``serve.*`` (``src/repro/launch/spans.py``), which the profiler records
on the host plane beside the device operations.  :func:`idle_split`
charges each moment of the device's idle time during the decode steps
to the innermost ``serve.*`` span open on the host then: ``serve.pull``
(a device→host read), any other span (host work: bookkeeping, fetch
accounting, table pushes, dispatch), or none (unattributed).

A program without the spans, or reports without the totals, read
nothing here: every function returns None.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import xtrace

PREFIX = "serve."
PULL = "serve.pull"
SETUP = "serve.setup"


def innermost(spans: Sequence[xtrace.Event], a: float, b: float
              ) -> List[Tuple[float, float, Optional[str]]]:
    """``[a, b)`` cut into stretches, each with the name of the
    innermost span open through it (the latest started of those that
    cover it), or None where no span is open."""
    cuts = sorted({a, b} | {t for _, s, e in spans for t in (s, e)
                            if a < t < b})
    evs = sorted(spans, key=lambda x: (x[1], -x[2]))
    out, open_, k = [], [], 0
    for s, e in zip(cuts, cuts[1:]):
        while k < len(evs) and evs[k][1] <= s:
            open_.append(evs[k])
            k += 1
        open_ = [x for x in open_ if x[2] > s]
        out.append((s, e, open_[-1][0] if open_ else None))
    return out


def idle_split(view: xtrace.TraceView) -> Optional[Dict[str, float]]:
    """Device-idle milliseconds per decode step, split by the innermost
    ``serve.*`` host span: ``{"pull", "host", "unattributed", "busy",
    "period"}`` plus one entry per span name (``"serve.emit"``, ...).

    The stretch read runs from the first to the last start of the decode
    steps wholly inside the window (``xtrace.program_runs``), so it
    holds whole step periods and ``period`` is ``decode_step_ms``'s; the
    parts sum to it.  None without two such steps or without a
    ``serve.*`` span in the stretch."""
    starts = [s for _, s, _ in xtrace.program_runs(view)[0]]
    if len(starts) < 2:
        return None
    a, b, n = starts[0], starts[-1], len(starts) - 1
    spans = [x for x in view.host
             if x[0].startswith(PREFIX) and x[2] > a and x[1] < b]
    if not spans:
        return None
    busy = xtrace.busy_intervals(view.ops[0], a, b)
    ms = 1e-6 / n
    out = {"pull": 0.0, "host": 0.0, "unattributed": 0.0,
           "busy": sum(e - s for s, e in busy) * ms,
           "period": (b - a) * ms}
    j = 0
    for s, e, name in innermost(spans, a, b):
        idle = e - s
        while j < len(busy) and busy[j][1] <= s:
            j += 1
        i = j
        while i < len(busy) and busy[i][0] < e:
            idle -= min(busy[i][1], e) - max(busy[i][0], s)
            i += 1
        if idle <= 0:
            continue
        if name is None:
            out["unattributed"] += idle * ms
            continue
        out["pull" if name == PULL else "host"] += idle * ms
        out[name] = out.get(name, 0.0) + idle * ms
    return out


def _reports(run, key: str) -> Optional[List]:
    got = [j.report.get(key) for j in run.jobs]
    return got if got and all(g is not None for g in got) else None


def pulls_per_step(run) -> Optional[float]:
    """Device→host reads in loop steps over loop steps, all jobs."""
    pulls = _reports(run, "pulls")
    if pulls is None:
        return None
    steps = sum(j.report["steps"] for j in run.jobs)
    return sum(pulls) / steps if steps else None


def queue_wait_ms(run) -> Optional[float]:
    """Mean wait from the ``serve()`` call to a request's claim, over
    every request of every job (milliseconds)."""
    waits = _reports(run, "queue_wait_s")
    if waits is None:
        return None
    vals = [v for w in waits for v in w.values()]
    return 1e3 * sum(vals) / len(vals) if vals else None


def call_setup_ms(run) -> Optional[float]:
    """Mean ``serve.setup`` span per job (milliseconds)."""
    spans = _reports(run, "spans")
    if spans is None or not all(SETUP in s for s in spans):
        return None
    return 1e3 * sum(s[SETUP]["total_s"] for s in spans) / len(spans)
