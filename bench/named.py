"""What a profiler trace says under the program's own names.

``trace.py`` reduces a trace to busy time and HLO op names.  The program
also names its work, and this module reads those names:

- each device op's HLO name stack, the ``tf_op`` stat of its event metadata
  (``jit(train_step)/transpose(jvp(forward))/while/...``).  The train step
  runs its loss under ``forward`` and its update under ``optimizer``
  (``core/stepfn.py``), so the stack gives each op's phase: ``forward``
  (``jvp(forward)``), ``backward`` (``transpose(jvp(forward))``, remat's
  recompute of the forward included), ``optimizer``, or ``other``.  A Pallas
  kernel's op is named by its ``pallas_call`` (``flash_attention_dq.3``),
  so kernels are told apart by name and phase: the forward kernel under
  ``backward`` is the recompute;
- the program's host spans (``train`` per step with its children
  ``train.batch``, ``train.dispatch``, ``train.observe``, ``train.log``,
  ``train.ckpt``; ``serve.admit``, ``serve.decode``, ``serve.read``,
  ``serve.retire`` per scheduler iteration): where chip 0 sat idle, by
  span.

``jax.profiler.ProfileData`` does not expose event-metadata stats, so the
``.xplane.pb`` is decoded here with a schema of the XPlane fields read.
Times follow ``ProfileData``: an event starts at its line's timestamp plus
its offset, both in whole ns.  Everything is clipped to the
``bench_window`` host span.
"""

from __future__ import annotations

import dataclasses
import functools
import re
import statistics
from collections import defaultdict
from typing import Dict, List, Tuple

from bench import trace

SPAN = re.compile(r"^(train|serve)(\.[a-z]+)?$")
PHASES = (("optimizer", "optimizer"),
          ("backward", "transpose(jvp(forward))"),
          ("forward", "jvp(forward)"))

_SCHEMA = {       # message → [(field, number, type, repeated, message type)]
    "XSpace": [("planes", 1, "message", True, "XPlane")],
    "XPlane": [("name", 2, "string", False, None),
               ("lines", 3, "message", True, "XLine"),
               ("event_metadata", 4, "message", True, "EventMetadataEntry"),
               ("stat_metadata", 5, "message", True, "StatMetadataEntry")],
    "XLine": [("name", 2, "string", False, None),
              ("timestamp_ns", 3, "int64", False, None),
              ("events", 4, "message", True, "XEvent")],
    "XEvent": [("metadata_id", 1, "int64", False, None),
               ("offset_ps", 2, "int64", False, None),
               ("duration_ps", 3, "int64", False, None)],
    "XStat": [("metadata_id", 1, "int64", False, None),
              ("str_value", 5, "string", False, None),
              ("ref_value", 7, "uint64", False, None)],
    "XEventMetadata": [("id", 1, "int64", False, None),
                       ("name", 2, "string", False, None),
                       ("stats", 5, "message", True, "XStat")],
    "XStatMetadata": [("id", 1, "int64", False, None),
                      ("name", 2, "string", False, None)],
    "EventMetadataEntry": [("key", 1, "int64", False, None),
                           ("value", 2, "message", False, "XEventMetadata")],
    "StatMetadataEntry": [("key", 1, "int64", False, None),
                          ("value", 2, "message", False, "XStatMetadata")],
}


@functools.cache
def xspace_class():
    """The ``XSpace`` message class, built from ``_SCHEMA``."""
    from google.protobuf import descriptor_pb2, descriptor_pool
    from google.protobuf import message_factory
    F = descriptor_pb2.FieldDescriptorProto
    types = {"string": F.TYPE_STRING, "int64": F.TYPE_INT64,
             "uint64": F.TYPE_UINT64, "message": F.TYPE_MESSAGE}
    fd = descriptor_pb2.FileDescriptorProto(
        name="bench_xplane.proto", package="bench_xplane", syntax="proto3")
    for msg, fields in _SCHEMA.items():
        m = fd.message_type.add(name=msg)
        for name, num, typ, rep, sub in fields:
            f = m.field.add(name=name, number=num, type=types[typ],
                            label=F.LABEL_REPEATED if rep
                            else F.LABEL_OPTIONAL)
            if sub:
                f.type_name = f".bench_xplane.{sub}"
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("bench_xplane.XSpace"))


def read_xspace(path):
    space = xspace_class()()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    return space


def phase(name_stack: str) -> str:
    parts = name_stack.split("/")
    for name, scope in PHASES:
        if scope in parts:
            return name
    return "other"


def kernel_name(op: str) -> str:
    """``flash_attention_dq.3`` → ``flash_attention_dq``."""
    return re.sub(r"\.\d+$", "", op)


def overlap(a: List[Tuple[float, float]], b: List[Tuple[float, float]]):
    """Length of the intersection of two sorted lists of disjoint
    intervals."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def merge(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


@dataclasses.dataclass
class Named:
    window_s: float
    devices: int
    busy_s: float                       # chip 0
    phase_s: Dict[str, float]           # phase → own seconds, over chips
    kernel_s: Dict[str, float]          # "<phase>/<kernel>" → own seconds
    spans: Dict[str, List[Tuple[float, float]]]   # name → (start, end) ns
    idle: List[Tuple[float, float]]     # chip 0's idle stretches, ns

    def count(self, name: str) -> int:
        """Spans of ``name`` that start in the window."""
        return len(self.spans.get(name, []))

    def idle_s(self, *names: str) -> float:
        """Chip 0's idle seconds inside the union of the ``names`` spans."""
        cover = merge([iv for n in names for iv in self.spans.get(n, [])])
        return overlap(self.idle, cover) / 1e9

    def idle_by_span(self) -> Dict[str, float]:
        """Idle seconds inside each span name (a step span holds its
        children's), and ``(none)``: idle outside every program span."""
        out = {n: self.idle_s(n) for n in self.spans}
        out["(none)"] = (sum(e - s for s, e in self.idle) / 1e9
                         - self.idle_s(*self.spans))
        return out

    def longest_idle(self, n: int = 5) -> List[Tuple[str, float]]:
        """The ``n`` longest idle stretches, each named by the innermost
        program span over its middle."""
        out = []
        for s, e in sorted(self.idle, key=lambda h: h[0] - h[1])[:n]:
            mid = (s + e) / 2
            cover = [(he - hs, name) for name, ivs in self.spans.items()
                     for hs, he in ivs if hs <= mid <= he]
            out.append((min(cover)[1] if cover else "(none)", (e - s) / 1e9))
        return out


def reduce(path, window: str = trace.WINDOW) -> Named:
    space = read_xspace(path)
    host = [pl for pl in space.planes if pl.name == trace.HOST_PLANE]
    devs = sorted(((int(trace.DEVICE_PLANE.match(pl.name).group(1)), pl)
                   for pl in space.planes
                   if trace.DEVICE_PLANE.match(pl.name)), key=lambda d: d[0])
    if not devs:
        raise ValueError(f"trace {path} has no TPU device plane")

    raw: List[Tuple[float, float, str]] = []
    for pl in host:
        names = {e.key: e.value.name for e in pl.event_metadata}
        for ln in pl.lines:
            for s, e, meta in _events(ln):
                raw.append((s, e, names.get(meta, "")))
    win = [(s, e) for s, e, n in raw if n == window]
    if not win:
        raise ValueError(f"trace {path} has no host event {window!r}")
    w0, w1 = win[0]
    spans: Dict[str, List[Tuple[float, float]]] = defaultdict(list)
    for s, e, n in sorted(raw):
        if SPAN.match(n) and w0 <= s < w1:
            spans[n].append((s, min(e, w1)))

    phase_s: Dict[str, float] = defaultdict(float)
    kernel_s: Dict[str, float] = defaultdict(float)
    busy = idle = None
    for idx, (_, pl) in enumerate(devs):
        stat_names = {e.key: e.value.name for e in pl.stat_metadata}
        meta = {e.key: _op_meta(e.value, stat_names)
                for e in pl.event_metadata}
        ops = []
        for ln in pl.lines:
            if ln.name == trace.OPS_LINE:
                ops += [(max(s, w0), min(e, w1), m) for s, e, m in _events(ln)
                        if e > w0 and s < w1 and e > s]
        own = trace.self_times([(s, e, i) for i, (s, e, _) in
                                enumerate(ops)])
        for i, (_, _, m) in enumerate(ops):
            op, stack, custom = meta[m]
            ph = phase(stack)
            phase_s[ph] += own[i] / 1e9
            if custom:
                kernel_s[f"{ph}/{kernel_name(op)}"] += own[i] / 1e9
        if idx == 0:
            busy_iv = merge([(s, e) for s, e, _ in ops])
            busy = sum(e - s for s, e in busy_iv) / 1e9
            idle = _holes(busy_iv, w0, w1)
    return Named(window_s=(w1 - w0) / 1e9, devices=len(devs), busy_s=busy,
                 phase_s=dict(phase_s), kernel_s=dict(kernel_s),
                 spans=dict(spans), idle=idle)


def _events(line):
    """(start ns, end ns, metadata id) of each event of a line, in whole
    ns as ``ProfileData`` gives them."""
    out = []
    for ev in line.events:
        s = float(line.timestamp_ns + ev.offset_ps // 1000)
        out.append((s, s + ev.duration_ps // 1000, ev.metadata_id))
    return out


def _op_meta(md, stat_names) -> Tuple[str, str, bool]:
    """(op name, name stack, is a custom call) of an ``XLA Ops`` event."""
    stats = {}
    for st in md.stats:
        stats[stat_names.get(st.metadata_id)] = (
            st.str_value or stat_names.get(st.ref_value, ""))
    stack = stats.get("tf_op", "").rsplit(":", 1)[0]
    return (trace.op_name(md.name), stack,
            stats.get("hlo_category") == "custom-call")


def _holes(busy: List[Tuple[float, float]], w0: float, w1: float):
    holes, t = [], w0
    for s, e in busy:
        if s > t:
            holes.append((t, s))
        t = max(t, e)
    if w1 > t:
        holes.append((t, w1))
    return holes


def summary(nm: Named, ctx: dict, stats: list) -> dict:
    """The named numbers of one traced run: ``nm`` from ``reduce``,
    the driver's ``ctx``, the window's ``ServingStats`` (serving)."""
    out = {}
    if ctx["driver"] == "train":
        steps = ctx["steps"]
        for ph in ("forward", "backward", "optimizer", "other"):
            out[f"step_{ph}_ms"] = (1e3 * nm.phase_s.get(ph, 0.0)
                                    / nm.devices / steps)
        out["busy_ms_per_step"] = 1e3 * nm.busy_s / steps
        children = [n for n in nm.spans if n.startswith("train.")]
        per = nm.count("train.dispatch")
        out["host_idle_ms.train"] = 1e3 * nm.idle_s(*children) / per
    else:
        steps = per = nm.count("serve.read")
        out["host_idle_ms.serve"] = 1e3 * nm.idle_s(*nm.spans) / per
        gaps = [g for s in stats for g in s.token_gaps()]
        out["inter_token_ms.serve"] = 1e3 * statistics.median(gaps)
        out["max_token_gap_ms"] = 1e3 * max(gaps)
        out["prefill_share.serve"] = (100.0 * sum(s.admit_s for s in stats)
                                      / ctx["window_s"])
    out["kernel_ms_per_step"] = {k: 1e3 * v / nm.devices / steps
                                 for k, v in sorted(nm.kernel_s.items())}
    out["idle_ms_per_step"] = {k: 1e3 * v / per
                               for k, v in nm.idle_by_span().items()}
    out["spans"] = {k: nm.count(k) for k in sorted(nm.spans)}
    out["span_ms"] = {k: 1e-6 * sum(e - s for s, e in v) / len(v)
                      for k, v in sorted(nm.spans.items())}
    out["longest_idle"] = nm.longest_idle(5)
    out["busy_s"], out["window_s"] = nm.busy_s, nm.window_s
    return out
