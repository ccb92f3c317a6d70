"""Device idle time put down to the host code that ran over it, and the
bounds a trace's flow events set on each device clock's offset.

An addition beside ``trace.py``, whose reduction it leaves as it is.  It
reads the harness's host spans (``trace.SPAN_PREFIXES``) and the program's
own (``PROGRAM_PREFIXES``: ``engine.*`` from ``serve/engine.py``,
``tuner.*`` from ``tune/selective.py``, ``tune/lm_study.py``,
``api/search.py`` and ``api/session.py``), places each device's ops on the
host clock with the offset ``trace.reduce_trace`` uses, and gives:

- ``self_idle_s``: {span name: device-idle seconds in the window whose
  innermost covering span is that name}.  The innermost span is the one
  that started last, the rule ``trace.py`` names its idle gaps by; idle
  time that no span covers is under ``"bench window"``.  The values add
  up to window - busy.
- ``offset_bounds_ns``: per device, the least and the greatest host -
  device offset the flows allow.  No program (``XLA Modules`` event)
  starts before the host's ``DoEnqueueProgram`` that enqueued it, and
  none ends after the host's ``CompleteCallbacks`` for it starts; each is
  matched to its program by the flow id it carries.
- ``spans`` and ``idle_gaps``: count, device and wall seconds per span
  name, and the longest idle gaps named by the innermost span over each,
  as ``trace.py`` gives them, over every span read here.
"""

from __future__ import annotations

import dataclasses
import heapq
from collections import defaultdict

import numpy as np

from . import trace as T

#: the program's own span names (``jax.profiler.TraceAnnotation``)
PROGRAM_PREFIXES = ("engine.", "tuner.")
ENQUEUE = "DoEnqueueProgram"
COMPLETE = "CompleteCallbacks"


@dataclasses.dataclass
class Split:
    window_s: float
    busy_s: float
    spans: dict             # name -> {"count", "device_s", "wall_s"}
    self_idle_s: dict       # name -> device-idle seconds it is innermost over
    offsets_ns: list        # per device: the offset the ops were placed by
    offset_bounds_ns: list  # per device: (least, greatest), or None
    idle_gaps: list         # [(span name, seconds)], longest first
    n_devices: int


def _stat(event, key):
    for name, value in event.stats:
        if name == key:
            return value
    return None


def host_events(pd):
    """The host planes' harness and program spans as (name, start, end),
    the launches' starts, and the starts of ``DoEnqueueProgram`` and
    ``CompleteCallbacks`` by flow id, all in ns."""
    spans, launches, enqueued, completed = [], [], {}, {}
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                name, s = e.name, float(e.start_ns)
                if name == T.LAUNCH:
                    launches.append(s)
                elif name == ENQUEUE:
                    enqueued[_stat(e, "_p")] = s
                elif name == COMPLETE:
                    completed[_stat(e, "_c")] = s
                elif name.startswith(T.SPAN_PREFIXES + PROGRAM_PREFIXES):
                    spans.append((name, s, s + float(e.duration_ns)))
    return spans, launches, enqueued, completed


def offset_bounds(modules, enqueued, completed):
    """(least, greatest) host - device offset for one device's programs,
    ``modules`` as (device start, duration, flow id); None without a
    matched flow."""
    lo = [enqueued[f] - s for s, _, f in modules if f in enqueued]
    hi = [completed[f] - (s + d) for s, d, f in modules if f in completed]
    if not lo or not hi:
        return None
    return (float(max(lo)), float(min(hi)))


def self_idle(ws, we, busy, spans):
    """{name: device-idle ns in [ws, we] whose innermost covering span is
    ``name``}, ``busy`` a ``trace._Busy`` over the device's busy intervals
    clipped to the window, ``spans`` as (name, start, end).  The innermost
    is the latest start, ties to the greater name, as ``trace.py`` labels
    its gaps; idle time no span covers goes under ``trace.WINDOW``."""
    bounds = [ws, we] + [t for _, s, e in spans for t in (s, e)]
    cuts = np.unique(np.clip(np.asarray(bounds, float), ws, we))
    idle = np.diff(cuts) - np.diff(busy.until(cuts))
    rank = {n: -i for i, n in enumerate(sorted({n for n, _, _ in spans}))}
    out = dict.fromkeys([n for n, _, _ in spans] + [T.WINDOW], 0.0)
    by_start = sorted(spans, key=lambda sp: sp[1])
    open_, j = [], 0
    for a, gap in zip(cuts[:-1], idle):
        while j < len(by_start) and by_start[j][1] <= a:
            name, s, e = by_start[j]
            heapq.heappush(open_, (-s, rank[name], e, name))
            j += 1
        while open_ and open_[0][2] <= a:
            heapq.heappop(open_)
        out[open_[0][3] if open_ else T.WINDOW] += float(gap)
    return out


def split_trace(path: str, *, top: int = 10, at_bound: bool = False) -> Split:
    """The split of one trace.  ``at_bound`` places each device's ops with
    the least offset its flows allow, in place of the offset in use: how
    far the split moves between the two is how much it rests on the
    alignment."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    host, launches, enqueued, completed = host_events(pd)
    win = [(s, e) for n, s, e in host if n == T.WINDOW]
    if not win:
        raise ValueError(f"trace has no {T.WINDOW!r} span")
    ws, we = win[0]
    spans = [(n, max(s, ws), min(e, we)) for n, s, e in host
             if n != T.WINDOW and e > ws and s < we]
    launches = np.sort(np.asarray(launches, float))

    busy_total, offsets, bounds, gaps = 0.0, [], [], []
    idle = defaultdict(float)
    per_span = defaultdict(lambda: {"count": 0, "device_s": 0.0,
                                    "wall_s": 0.0})
    nd = 0
    for plane in pd.planes:
        if not plane.name.startswith("/device:") or "CUSTOM" in plane.name:
            continue
        lines = {line.name: list(line.events) for line in plane.lines}
        if not lines.get("XLA Ops"):
            continue
        nd += 1
        modules = [(float(e.start_ns), float(e.duration_ns), _stat(e, "_c"))
                   for e in lines.get("XLA Modules", [])]
        mods = sorted(s for s, _, _ in modules)
        n = min(len(mods), len(launches))
        # the offset trace.reduce_trace uses, computed as it computes it
        off = float(np.max(launches[:n] - np.asarray(mods[:n]))) if n else 0.0
        bounds.append(offset_bounds(modules, enqueued, completed))
        if at_bound and bounds[-1] is not None:
            off = bounds[-1][0]
        offsets.append(off)
        st = np.asarray([float(e.start_ns) for e in lines["XLA Ops"]]) + off
        en = st + np.asarray([float(e.duration_ns) for e in lines["XLA Ops"]])
        us, ue = T._union(st, en)
        us, ue = np.clip(us, ws, we), np.clip(ue, ws, we)
        keep = ue > us
        busy = T._Busy(us[keep], ue[keep])
        busy_total += float(np.sum(ue[keep] - us[keep]))
        gs, ge = np.append(ws, busy.ue), np.append(busy.us, we)
        gaps.extend((a, b) for a, b in zip(gs, ge) if b > a)
        for name, ns in self_idle(ws, we, busy, spans).items():
            idle[name] += ns
        if spans:
            dev = busy.between(np.asarray([s for _, s, _ in spans]),
                               np.asarray([e for _, _, e in spans]))
            for (name, s, e), dv in zip(spans, dev):
                rec = per_span[name]
                rec["count"] += 1
                rec["device_s"] += float(dv) * 1e-9
                rec["wall_s"] += (e - s) * 1e-9
    if not nd:
        raise ValueError("trace has no device plane with XLA Ops")
    for rec in per_span.values():
        rec["count"] //= nd
        rec["device_s"] /= nd
        rec["wall_s"] /= nd
    named = []
    for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:top]:
        mid = 0.5 * (a + b)
        cover = [(s, n) for n, s, e in spans if s <= mid <= e]
        named.append([max(cover)[1] if cover else T.WINDOW,
                      float(b - a) * 1e-9])
    return Split(window_s=(we - ws) * 1e-9, busy_s=busy_total / nd * 1e-9,
                 spans=dict(per_span),
                 self_idle_s={k: v / nd * 1e-9 for k, v in idle.items()},
                 offsets_ns=offsets, offset_bounds_ns=bounds,
                 idle_gaps=named, n_devices=nd)


def idle_pct(ctx, prefixes):
    """100 x the device-idle time that spans named by ``prefixes`` are
    innermost over, over the window; None where the trace holds none of
    them (a reduction without ``self_idle_s`` holds none)."""
    split = getattr(ctx.trace, "self_idle_s", None) or {}
    found = [n for n in split if n.startswith(prefixes)]
    if not found or ctx.trace.window_s <= 0:
        return None
    return 100.0 * sum(split[n] for n in found) / ctx.trace.window_s
