"""From a profiler trace to the numbers the per-layer readers need.

A traced run wraps its window in a host annotation named ``WINDOW``;
the JAX profiler writes an ``.xplane.pb``, which :func:`load` turns into
a :class:`TraceView`: the window's bounds and three lists of events
(device operations, device program executions, host activity), each
event a ``(name, start_ns, end_ns)`` triple on the trace's one clock.
Everything after :func:`load` is plain Python over those lists, so the
tests check it on a recorded view (``tests/data``) without a chip.

Kernels are found by the name they carry in the trace (the decode
gather kernel's operations start with ``sata_decode_attention``), and
programs by their jit names: a program execution that runs the decode
kernel is a decode step; one that does not, and whose jit name is the
serving loop's prefill (``prefill`` or the loop's anonymous
``lambda``), is a prefill.
"""
from __future__ import annotations

import bisect
import dataclasses
import gzip
import json
import re
from typing import Dict, List, Optional, Sequence, Tuple

WINDOW = "chipbench.window"
DECODE_KERNEL = "sata_decode_attention"
PREFILL_JIT = re.compile(r"prefill|lambda")
_DEVICE_PLANE = re.compile(r"^/device:[A-Z]+:\d+$")

Event = Tuple[str, float, float]          # (name, start_ns, end_ns)


@dataclasses.dataclass
class TraceView:
    t0: float                             # window start, ns
    t1: float                             # window end, ns
    ops: List[List[Event]]                # per device: operations
    modules: List[List[Event]]            # per device: program runs
    host: List[Event]                     # host activity, all threads

    @property
    def window_s(self) -> float:
        return (self.t1 - self.t0) * 1e-9

    def to_json(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_json(cls, d: dict) -> "TraceView":
        tup = lambda evs: [tuple(e) for e in evs]   # noqa: E731
        return cls(d["t0"], d["t1"], [tup(o) for o in d["ops"]],
                   [tup(m) for m in d["modules"]], tup(d["host"]))

    def save(self, path: str) -> None:
        with gzip.open(path, "wt") as f:
            json.dump(self.to_json(), f)

    @classmethod
    def read(cls, path: str) -> "TraceView":
        with gzip.open(path, "rt") as f:
            return cls.from_json(json.load(f))


def load(xplane_path: str) -> TraceView:
    """Read an ``.xplane.pb`` into a :class:`TraceView` of the events
    that overlap the window annotation.  Raises ``ValueError`` when the trace has no
    window or no device."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(xplane_path)
    win = None
    host: List[Event] = []
    ops: List[List[Event]] = []
    modules: List[List[Event]] = []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    ev = (e.name, float(e.start_ns), float(e.end_ns))
                    if e.name == WINDOW:
                        win = ev
                    else:
                        host.append(ev)
        elif _DEVICE_PLANE.match(plane.name):
            lines = {line.name: line for line in plane.lines}
            if "XLA Ops" not in lines:
                continue
            ops.append([(op_name(e.name), float(e.start_ns),
                         float(e.end_ns)) for e in lines["XLA Ops"].events])
            mod = lines.get("XLA Modules")
            modules.append([] if mod is None else
                           [(e.name, float(e.start_ns), float(e.end_ns))
                            for e in mod.events])
    if win is None:
        raise ValueError(f"no {WINDOW!r} annotation in {xplane_path}")
    if not ops:
        raise ValueError(f"no device operations in {xplane_path}")
    t0, t1 = win[1], win[2]
    return TraceView(t0, t1, [_overlapping(o, t0, t1) for o in ops],
                     [_overlapping(m, t0, t1) for m in modules],
                     _overlapping(host, t0, t1))


def op_name(name: str) -> str:
    """The HLO instruction's name: a TPU trace may name an operation by
    its whole HLO text (``%fusion.3 = bf16[...] fusion(...)``)."""
    return name.split(" = ", 1)[0].lstrip("%")


def _overlapping(evs: Sequence[Event], t0: float, t1: float
                 ) -> List[Event]:
    """The events that overlap ``[t0, t1)``, unclipped, by start."""
    return sorted((e for e in evs if e[2] > t0 and e[1] < t1),
                  key=lambda e: e[1])


def _inside(view: "TraceView", evs: Sequence[Event]) -> List[Event]:
    return [e for e in evs if e[1] >= view.t0 and e[2] <= view.t1]


def busy_intervals(ops: Sequence[Event], t0: float, t1: float
                   ) -> List[Tuple[float, float]]:
    """Union of the operations' intervals clipped to ``[t0, t1)``."""
    out: List[List[float]] = []
    for _, s, e in sorted(ops, key=lambda x: x[1]):
        s, e = max(s, t0), min(e, t1)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_s(view: TraceView) -> float:
    """Seconds in which some operation ran, averaged over devices."""
    tot = [sum(e - s for s, e in busy_intervals(o, view.t0, view.t1))
           for o in view.ops]
    return sum(tot) / len(tot) * 1e-9


def idle_share(view: TraceView) -> Optional[float]:
    """Percent of the window with no operation running on the device
    (averaged over devices)."""
    if view.t1 <= view.t0:
        return None
    return 100.0 * (1.0 - busy_s(view) / view.window_s)


def _contains_kernel(mod: Event, kernel_starts: Sequence[float]) -> bool:
    i = bisect.bisect_left(kernel_starts, mod[1])
    return i < len(kernel_starts) and kernel_starts[i] < mod[2]


def program_runs(view: TraceView) -> Tuple[List[Event], List[Event]]:
    """(decode steps, prefills) that ran on device 0 wholly inside the
    window."""
    ops, mods = view.ops[0], _inside(view, view.modules[0])
    ks = sorted(s for n, s, _ in ops if n.startswith(DECODE_KERNEL))
    decode, prefill = [], []
    for m in mods:
        if _contains_kernel(m, ks):
            decode.append(m)
        elif PREFILL_JIT.search(m[0]):
            prefill.append(m)
    return decode, prefill


def decode_period_s(view: TraceView) -> Optional[float]:
    """Seconds from one decode step's start to the next, over the decode
    steps wholly inside the window, taken between the first and last
    step's starts so a step cut by the window's edge does not count."""
    starts = [s for _, s, _ in program_runs(view)[0]]
    if len(starts) < 2:
        return None
    return (starts[-1] - starts[0]) / (len(starts) - 1) * 1e-9


def kernel_time(view: TraceView) -> Tuple[int, float]:
    """(calls, summed device seconds) of the decode kernel's operations
    on device 0 wholly inside the window."""
    evs = [(s, e) for n, s, e in _inside(view, view.ops[0])
           if n.startswith(DECODE_KERNEL)]
    return len(evs), sum(e - s for s, e in evs) * 1e-9


def leaf_ops(ops: Sequence[Event]) -> List[Event]:
    """The operations that contain no other operation: a loop or call
    (``while``, ``conditional``) spans the operations of its body."""
    evs = sorted(ops, key=lambda x: (x[1], -x[2]))
    return [e for i, e in enumerate(evs)
            if not (i + 1 < len(evs) and evs[i + 1][1] < e[2]
                    and evs[i + 1][2] <= e[2])]


def top_ops(view: TraceView, n: int = 10) -> List[List]:
    """The ``n`` device operations (device 0, loops and calls left out
    for the operations inside them) that took the most time, summed by
    name: ``[[name, seconds], ...]``."""
    tot: Dict[str, float] = {}
    for name, s, e in leaf_ops(view.ops[0]):
        d = min(e, view.t1) - max(s, view.t0)
        tot[name] = tot.get(name, 0.0) + d * 1e-9
    return [[k, v] for k, v in
            sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(view: TraceView, n: int = 10) -> List[List]:
    """The ``n`` longest stretches of the window in which device 0 ran
    nothing, each named by what the host was doing then:
    ``[[name, seconds], ...]``."""
    busy = busy_intervals(view.ops[0], view.t0, view.t1)
    gaps, t = [], view.t0
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if view.t1 > t:
        gaps.append((t, view.t1))
    gaps.sort(key=lambda g: g[0] - g[1])
    return [[host_activity(view.host, s, e), (e - s) * 1e-9]
            for s, e in gaps[:n]]


def host_activity(host: Sequence[Event], s: float, e: float) -> str:
    """Name of the host event that best explains ``[s, e)``: of those
    that cover at least half of it the shortest (the most specific),
    else the one that overlaps it most."""
    best, best_ov = None, 0.0
    covering = []
    for name, hs, he in host:
        ov = min(he, e) - max(hs, s)
        if ov <= 0:
            continue
        if ov >= 0.5 * (e - s):
            covering.append((he - hs, name))
        if ov > best_ov:
            best, best_ov = name, ov
    if covering:
        return min(covering)[1]
    return best or "none"
