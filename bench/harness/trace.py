"""Reduce a profiler trace (``.xplane.pb``) to the numbers the per-layer
metrics read.

- The window is the harness's ``bench window`` host span.
- Device busy time is the union of the device's ``XLA Ops`` intervals in
  the window (averaged over the chips traced); idle share = 1 - busy /
  window.
- The device clock of a TPU trace runs apart from the host's (about a
  millisecond on a v5e).  Each device's offset is the smallest shift that
  starts no program (``XLA Modules`` event) before the host call that
  launched it (``PJRT_LoadedExecutable_Execute``), matched in order.
- Device time per host span: the busy union inside each harness span,
  summed by span name (``kernel <sig>``, ``reference``, ...).
- The longest idle gaps in the window, each named by the innermost
  harness span that covers its middle; the device ops that took most time.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
from collections import defaultdict

import numpy as np

#: host span names (or prefixes) that the harness writes; see spans.py
SPAN_PREFIXES = ("kernel ", "reference", "skip bookkeeping", "campaign",
                 "train step", "batch fetch", "bench window")
LAUNCH = "PJRT_LoadedExecutable_Execute"
WINDOW = "bench window"


@dataclasses.dataclass
class Reduction:
    window_s: float
    busy_s: float
    top_ops: list          # [(op name, seconds)], longest first
    idle_gaps: list        # [(span name, seconds)], longest first
    spans: dict            # name -> {"count", "device_s", "wall_s"}
    offsets_ns: list       # per device plane: host - device clock
    n_devices: int

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def find_xplane(directory: str) -> str:
    found = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return max(found, key=os.path.getmtime)


def short_op_name(name: str) -> str:
    """'%convolution_tanh_fusion.3 = f32[...] fusion(...)' ->
    'convolution_tanh_fusion'."""
    head = name.split(" = ", 1)[0].lstrip("%").strip()
    return re.sub(r"\.\d+$", "", head)


def _self_times(ops):
    """Each op's duration less the ops nested inside it on the same line
    (a ``while`` holds its body's ops)."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i][1], -ops[i][2]))
    self_t = [d for _, _, d in ops]
    stack = []
    for i in order:
        s, e = ops[i][1], ops[i][1] + ops[i][2]
        while stack and ops[stack[-1]][1] + ops[stack[-1]][2] <= s:
            stack.pop()
        if stack and e <= ops[stack[-1]][1] + ops[stack[-1]][2]:
            self_t[stack[-1]] -= ops[i][2]
        stack.append(i)
    return self_t


def _union(starts, ends):
    """Merge intervals; returns sorted disjoint (starts, ends)."""
    if len(starts) == 0:
        return np.zeros(0), np.zeros(0)
    order = np.argsort(starts, kind="stable")
    s, e = np.asarray(starts, float)[order], np.asarray(ends, float)[order]
    run_end = np.maximum.accumulate(e)
    new = np.ones(len(s), bool)
    new[1:] = s[1:] > run_end[:-1]
    idx = np.nonzero(new)[0]
    us = s[idx]
    ue = np.append(run_end[idx[1:] - 1], run_end[-1])
    return us, ue


class _Busy:
    """Busy time of a disjoint interval set up to any time t."""

    def __init__(self, us, ue):
        self.us, self.ue = us, ue
        self.cum = np.concatenate([[0.0], np.cumsum(ue - us)])

    def until(self, t):
        t = np.asarray(t, float)
        i = np.searchsorted(self.us, t, side="right")   # intervals started
        full = self.cum[np.maximum(i - 1, 0)]
        last_s = self.us[np.maximum(i - 1, 0)] if len(self.us) else 0.0
        last_e = self.ue[np.maximum(i - 1, 0)] if len(self.ue) else 0.0
        part = np.clip(np.minimum(t, last_e) - last_s, 0.0, None)
        return np.where(i > 0, full + part, 0.0)

    def between(self, a, b):
        return self.until(b) - self.until(a)


def _events(line):
    return [(e.name, float(e.start_ns), float(e.duration_ns))
            for e in line.events]


def reduce_trace(path: str, *, top: int = 10) -> Reduction:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    host_spans, launches, devices = [], [], []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for name, s, d in _events(line):
                    if name == LAUNCH:
                        launches.append(s)
                    elif name.startswith(SPAN_PREFIXES):
                        host_spans.append((name, s, s + d))
        elif plane.name.startswith("/device:") and "CUSTOM" not in plane.name:
            lines = {l.name: _events(l) for l in plane.lines}
            if lines.get("XLA Ops"):
                devices.append(lines)
    win = [(s, e) for n, s, e in host_spans if n == WINDOW]
    if not win:
        raise ValueError(f"trace has no {WINDOW!r} span")
    ws, we = win[0]
    if not devices:
        raise ValueError("trace has no device plane with XLA Ops")
    launches = np.sort(np.asarray(launches, float))
    spans = [(n, s, e) for n, s, e in host_spans if n != WINDOW
             and e > ws and s < we]

    busy_total = 0.0
    op_time = defaultdict(float)
    gaps_all = []
    per_span = defaultdict(lambda: {"count": 0, "device_s": 0.0,
                                    "wall_s": 0.0})
    offsets = []
    for lines in devices:
        mods = sorted(s for _, s, _ in lines.get("XLA Modules", []))
        n = min(len(mods), len(launches))
        off = float(np.max(launches[:n] - np.asarray(mods[:n]))) if n else 0.0
        offsets.append(off)
        ops = lines["XLA Ops"]
        st = np.asarray([s for _, s, _ in ops]) + off
        en = st + np.asarray([d for _, _, d in ops])
        us, ue = _union(st, en)
        us, ue = np.clip(us, ws, we), np.clip(ue, ws, we)
        keep = ue > us
        us, ue = us[keep], ue[keep]
        busy = _Busy(us, ue)
        busy_total += float(np.sum(ue - us))
        for (name, s, d), a, b, own in zip(ops, st, en, _self_times(ops)):
            if b > ws and a < we:
                op_time[short_op_name(name)] += own * (
                    (min(b, we) - max(a, ws)) / d if d else 0.0)
        # idle gaps inside the window
        gs = np.concatenate([[ws], ue])
        ge = np.concatenate([us, [we]])
        ok = ge > gs
        gaps_all.extend(zip(gs[ok], ge[ok]))
        if spans:
            a = np.asarray([max(s, ws) for _, s, _ in spans])
            b = np.asarray([min(e, we) for _, _, e in spans])
            dev = busy.between(a, b)
            for (name, s, e), x, y, dv in zip(spans, a, b, dev):
                rec = per_span[name]
                rec["count"] += 1
                rec["device_s"] += float(dv) * 1e-9
                rec["wall_s"] += float(y - x) * 1e-9
    nd = len(devices)
    for rec in per_span.values():
        rec["count"] //= nd
        rec["device_s"] /= nd
        rec["wall_s"] /= nd
    gaps_all.sort(key=lambda g: g[0] - g[1])
    named = []
    for gs_, ge_ in gaps_all[:top]:
        mid = 0.5 * (gs_ + ge_)
        cover = [(s, n) for n, s, e in spans if s <= mid <= e]
        label = max(cover)[1] if cover else "bench window"
        named.append([label, float(ge_ - gs_) * 1e-9])
    tops = sorted(op_time.items(), key=lambda kv: -kv[1])[:top]
    return Reduction(
        window_s=float(we - ws) * 1e-9, busy_s=busy_total / nd * 1e-9,
        top_ops=[[k, float(v) / nd * 1e-9] for k, v in tops],
        idle_gaps=named, spans=dict(per_span), offsets_ns=offsets,
        n_devices=nd)
