"""Reduce a profiler trace (``.xplane.pb``) to what the per-layer metrics read.

A TPU trace holds one plane per chip (``/device:TPU:<n>``) whose ``XLA Ops``
line has one event per HLO operation that ran, named by its HLO text
(``%flash_attention.54 = (...) custom-call(...)``), and host planes whose
lines are threads.  Device and host events share one clock.  Loop
operations (``%while``) enclose the operations of their body, so busy time
is a union and an operation's own time excludes what it encloses.

The window is the host event ``bench_window`` the harness opens around the
traced stretch; everything is clipped to it.

A collective (``COLLECTIVE``) is exposed where it runs and no other
operation does.  The other operations counted are those that enclose no
other operation: a loop spans the collectives of its body and is no work
of its own.
"""

from __future__ import annotations

import dataclasses
import glob
import re
from collections import defaultdict
from pathlib import Path
from typing import Dict, List, Tuple

WINDOW = "bench_window"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
COLLECTIVE = re.compile(r"^(all-reduce|all-gather|reduce-scatter|"
                        r"collective-permute|all-to-all)(-start|-done)?"
                        r"(\.\d+)?$")


def op_name(event_name: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` → ``fusion.12``."""
    head = event_name.split(" = ", 1)[0].strip()
    return head[1:] if head.startswith("%") else head


def union_length(intervals: List[Tuple[float, float]]) -> float:
    total, end = 0.0, float("-inf")
    for s, e in sorted(intervals):
        if e <= end:
            continue
        total += e - max(s, end)
        end = e
    return total


def self_times(events: List[Tuple[float, float, str]]) -> Dict[str, float]:
    """Own time of each op name: its duration less that of the events it
    encloses on the same line."""
    out: Dict[str, float] = defaultdict(float)
    stack: List[list] = []          # [start, end, name, child_time]
    for s, e, name in sorted(events, key=lambda t: (t[0], -(t[1] - t[0]))):
        while stack and s >= stack[-1][1]:
            top = stack.pop()
            out[top[2]] += (top[1] - top[0]) - top[3]
        if stack:
            stack[-1][3] += e - s
        stack.append([s, e, name, 0.0])
    for top in stack:
        out[top[2]] += (top[1] - top[0]) - top[3]
    return dict(out)


def leaves(events: List[Tuple[float, float, str]]):
    """The events that enclose no other event of the list."""
    out, stack = [], []             # stack: [event, encloses another]
    for ev in sorted(events, key=lambda t: (t[0], -(t[1] - t[0]))):
        while stack and ev[0] >= stack[-1][0][1]:
            top = stack.pop()
            if not top[1]:
                out.append(top[0])
        if stack and ev[1] <= stack[-1][0][1]:
            stack[-1][1] = True
        stack.append([ev, False])
    out.extend(top[0] for top in stack if not top[1])
    return out


def collective_times(events: List[Tuple[float, float, str]]):
    """(time a collective runs, time one runs and no other operation does)
    on one chip's line, in the events' unit."""
    coll = [(s, e) for s, e, n in events if COLLECTIVE.match(n)]
    other = [(s, e) for s, e, _ in
             leaves([ev for ev in events if not COLLECTIVE.match(ev[2])])]
    return (union_length(coll),
            union_length(coll + other) - union_length(other))


@dataclasses.dataclass
class Reduced:
    window_s: float
    devices: int
    busy_s: List[float]                  # per chip
    op_self_s: Dict[str, float]          # op name → own seconds, summed over chips
    idle_gaps: List[Tuple[str, float]]   # longest gaps on chip 0, by host span
    collective_s: List[float]            # per chip: a collective runs
    collective_exposed_s: List[float]    # per chip: one runs, nothing else

    @property
    def mean_busy_s(self) -> float:
        return sum(self.busy_s) / len(self.busy_s)

    def kernel_seconds(self, pattern: str) -> float:
        rx = re.compile(pattern)
        return sum(t for n, t in self.op_self_s.items() if rx.search(n))

    def top_ops(self, n: int = 10) -> List[Tuple[str, float]]:
        by = sorted(self.op_self_s.items(), key=lambda kv: -kv[1])[:n]
        return [[k, v / self.devices] for k, v in by]


def find_xplane(log_dir) -> Path:
    hits = sorted(glob.glob(str(Path(log_dir) / "**" / "*.xplane.pb"),
                            recursive=True))
    if not hits:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return Path(hits[-1])


def _events(line):
    return [(float(e.start_ns), float(e.start_ns) + float(e.duration_ns),
             e.name) for e in line.events]


def reduce(path, window: str = WINDOW, n_gaps: int = 10) -> Reduced:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    planes = list(pd.planes)

    host_spans: List[Tuple[float, float, str]] = []
    w0 = w1 = None
    for pl in planes:
        if pl.name != HOST_PLANE:
            continue
        for ln in pl.lines:
            for s, e, n in _events(ln):
                if n == window:
                    w0, w1 = s, e
                elif e > s:
                    host_spans.append((s, e, n))
    if w0 is None:
        raise ValueError(f"trace {path} has no host event {window!r}")

    def clip(evs):
        return [(max(s, w0), min(e, w1), n) for s, e, n in evs
                if e > w0 and s < w1 and e > s]

    devs = sorted((int(DEVICE_PLANE.match(pl.name).group(1)), pl)
                  for pl in planes if DEVICE_PLANE.match(pl.name))
    if not devs:
        raise ValueError(f"trace {path} has no TPU device plane")
    busy, coll, exposed = [], [], []
    op_self: Dict[str, float] = defaultdict(float)
    gaps: List[Tuple[str, float]] = []
    for idx, (_, pl) in enumerate(devs):
        lines = {ln.name: ln for ln in pl.lines}
        ops = clip([(s, e, op_name(n)) for s, e, n in
                    _events(lines[OPS_LINE])]) if OPS_LINE in lines else []
        iv = [(s, e) for s, e, _ in ops]
        busy.append(union_length(iv) / 1e9)
        c, x = collective_times(ops)
        coll.append(c / 1e9)
        exposed.append(x / 1e9)
        for n, t in self_times(ops).items():
            op_self[n] += t / 1e9
        if idx == 0:
            gaps = _idle_gaps(sorted(iv), w0, w1, host_spans, n_gaps)
    return Reduced(window_s=(w1 - w0) / 1e9, devices=len(devs), busy_s=busy,
                   op_self_s=dict(op_self), idle_gaps=gaps,
                   collective_s=coll, collective_exposed_s=exposed)


def _idle_gaps(busy: List[Tuple[float, float]], w0: float, w1: float,
               host_spans, n: int) -> List[Tuple[str, float]]:
    """The ``n`` longest stretches of the window in which the chip ran
    nothing, each named by the innermost host span covering its middle."""
    holes, t = [], w0
    for s, e in busy:
        if s > t:
            holes.append((t, s))
        t = max(t, e)
    if w1 > t:
        holes.append((t, w1))
    holes.sort(key=lambda h: -(h[1] - h[0]))
    out = []
    for s, e in holes[:n]:
        mid = (s + e) / 2
        cover = [(he - hs, name) for hs, he, name in host_spans
                 if hs <= mid <= he]
        label = min(cover)[1] if cover else "no host span"
        out.append([label, (e - s) / 1e9])
    return out
