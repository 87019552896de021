"""From a profiler trace to device time, idle gaps and per-step times.

The trace is reduced in two steps, so the second can be checked on a small
recorded file without a chip:

* :func:`load` reads an ``.xplane.pb`` into :class:`Trace`: the device's
  operations and compiled modules (per device plane, lines ``XLA Ops``
  and ``XLA Modules``; an operation keeps the HLO name before `` = ``) and
  the benchmark's host spans (names with a dot, such as ``engine.step``,
  as :mod:`bench.driver` writes them);
* :func:`reduce` cuts all of it to the traced window (the host span
  ``bench.window``) and returns busy time, per-operation device time,
  every module run with the operations it contains, and the longest idle
  gaps named by the innermost host span open at their middle.
"""
from __future__ import annotations

import bisect
import json
import re
from dataclasses import dataclass, field

#: host span that brackets the measured window in a traced run
WINDOW_SPAN = "bench.window"
_SPAN_NAME = re.compile(r"^[a-z_]+\.[a-z_]+$")


@dataclass
class Trace:
    """Intervals in nanoseconds: ``(name, start, end)``."""

    ops: dict[str, list[tuple[str, float, float]]] = field(default_factory=dict)
    modules: dict[str, list[tuple[str, float, float]]] = field(default_factory=dict)
    spans: list[tuple[str, float, float]] = field(default_factory=list)

    @classmethod
    def from_json(cls, text: str) -> "Trace":
        d = json.loads(text)
        return cls(
            ops={k: [tuple(e) for e in v] for k, v in d["ops"].items()},
            modules={k: [tuple(e) for e in v] for k, v in d["modules"].items()},
            spans=[tuple(e) for e in d["spans"]],
        )


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    tr = Trace()
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            for line in plane.lines:
                into = {"XLA Ops": tr.ops, "XLA Modules": tr.modules}.get(line.name)
                if into is None:
                    continue
                evs = into.setdefault(plane.name, [])
                for e in line.events:
                    name = e.name.split(" = ")[0]
                    evs.append((name, e.start_ns, e.start_ns + e.duration_ns))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if _SPAN_NAME.match(e.name):
                        tr.spans.append((e.name, e.start_ns, e.start_ns + e.duration_ns))
    return tr


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def module_key(name: str) -> str:
    """``jit__paged_decode_impl(123)`` -> ``_paged_decode_impl``."""
    name = name.split("(")[0].strip()
    return name[4:] if name.startswith("jit_") else name


def op_stem(name: str) -> str:
    """``%paged_attention.6`` -> ``paged_attention``."""
    return re.sub(r"\.\d+$", "", name.lstrip("%"))


@dataclass
class ModuleRun:
    key: str                 # module_key of the compiled program
    seconds: float
    ops: frozenset[str]      # stems of the operations run inside it


@dataclass
class Reduced:
    window_s: float
    busy_s: float                        # mean over device planes
    devices: int
    modules: list[ModuleRun]             # every module run in the window
    op_s: dict[str, float]               # operation -> device seconds
    idle_gaps: list[tuple[str, float]]   # longest first

    def top_ops(self, n: int = 10) -> list[list]:
        return [[k, v] for k, v in sorted(self.op_s.items(), key=lambda kv: -kv[1])[:n]]


def reduce(tr: Trace, n_gaps: int = 10) -> Reduced | None:
    """Reduce to the window; ``None`` when the trace holds no window or no
    device operation in it."""
    win = [(s, e) for n, s, e in tr.spans if n == WINDOW_SPAN]
    if not win or not tr.ops:
        return None
    w0, w1 = win[0]

    def clip(evs):
        return [(n, max(s, w0), min(e, w1)) for n, s, e in evs if e > w0 and s < w1]

    busy, gaps_all = [], []
    op_s: dict[str, float] = {}
    for plane, evs in tr.ops.items():
        evs = clip(evs)
        if not evs:
            continue
        u = _union([(s, e) for _, s, e in evs])
        busy.append(sum(e - s for s, e in u))
        for n, s, e in evs:
            op_s[n] = op_s.get(n, 0.0) + (e - s) / 1e9
        edges = [w0] + [x for iv in u for x in iv] + [w1]
        gaps_all += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                     if edges[i + 1] > edges[i]]
    if not busy:
        return None
    modules = []
    for plane, evs in tr.modules.items():
        ops = sorted(clip(tr.ops.get(plane, [])), key=lambda o: o[1])
        starts = [o[1] for o in ops]
        for n, s, e in evs:
            if s < w0 or e > w1:        # only runs whole inside the window
                continue
            lo = bisect.bisect_left(starts, s)
            hi = bisect.bisect_right(starts, e)
            inside = frozenset(op_stem(o[0]) for o in ops[lo:hi] if o[2] <= e)
            modules.append(ModuleRun(module_key(n), (e - s) / 1e9, inside))
    spans = [sp for sp in tr.spans if sp[0] != WINDOW_SPAN]
    gaps = []
    for s, e in sorted(gaps_all, key=lambda g: g[0] - g[1])[:n_gaps]:
        mid = (s + e) / 2
        open_ = [sp for sp in spans if sp[1] <= mid < sp[2]]
        name = max(open_, key=lambda sp: sp[1])[0] if open_ else "host"
        gaps.append((name, (e - s) / 1e9))
    return Reduced(
        window_s=(w1 - w0) / 1e9, busy_s=sum(busy) / len(busy) / 1e9,
        devices=len(busy), modules=modules, op_s=op_s, idle_gaps=gaps,
    )
