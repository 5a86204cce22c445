"""From a profiler trace to numbers: the device's busy time as the UNION of
the intervals in which an operation ran (overlapping ops are not summed
twice), the idle share, each idle gap attributed to what the host was
doing in it, and time by operation name.

Copied in idea from ``minips_tpu/utils/trace_analysis.py`` and corrected:
that one sums overlapping ops and knows no idle share. The reduction works
on plain tuples so a small recorded trace can test it (``events_from_json``
reads the form the tests keep); ``events_from_xplane`` reads what
``jax.profiler`` writes, with nothing but JAX.
"""

from __future__ import annotations

import glob
import json
import os
import re
from dataclasses import dataclass, field

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)")
OPS_LINE = "XLA Ops"
HOST_SPAN_PREFIX = "bench."


@dataclass
class Op:
    name: str          # e.g. fusion.9
    category: str      # the HLO opcode: fusion, custom-call, all-reduce
    detail: str        # result shapes <- operand shapes
    start: float       # seconds
    dur: float         # seconds

    @property
    def label(self) -> str:
        """A name that says what the op is: name, category and shapes."""
        tail = re.sub(r"[^A-Za-z0-9]+", "_", self.detail)[:72].strip("_")
        parts = [self.name, self.category, tail]
        return "__".join(p for p in parts if p)


@dataclass
class Trace:
    devices: dict = field(default_factory=dict)   # ordinal -> [Op]
    spans: list = field(default_factory=list)     # (name, start, dur) host


OPCODE = re.compile(r" ([a-z][a-z0-9\-]*)\(")
SHAPE = re.compile(r"\b[a-z]+[0-9]+\[[0-9,]*\]")


def parse_hlo(text: str) -> tuple:
    """(name, opcode, shapes) of an op event. libtpu names an op event by
    its whole HLO line: '%fusion.9 = f32[64,10]{0,1:T(8,128)} fusion(f32[64,
    10]{..} %p, s32[8]{..} %i), kind=kCustom, calls=%fused_computation.9'.
    The opcode is the category; ``shapes`` is the result's and then the
    operands', layouts dropped. A plain name comes back as it is."""
    m = re.match(r"%?([\w.\-]+) = (.*)$", text, re.S)
    if not m:
        return text, "", ""
    name, rest = m.group(1), m.group(2)
    op = OPCODE.search(rest)
    opcode = op.group(1) if op else ""
    head = rest[: op.start()] if op else rest
    args = rest[op.end():].split("), ", 1)[0] if op else ""
    result = " ".join(SHAPE.findall(head))
    operands = ",".join(SHAPE.findall(args))
    return name, opcode, (result + " <- " + operands if operands
                          else result)


def events_from_xplane(path: str) -> Trace:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    tr = Trace()
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            ops = tr.devices.setdefault(int(m.group(1)), [])
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for e in line.events:
                    name, opcode, shapes = parse_hlo(e.name)
                    ops.append(Op(name, opcode, shapes,
                                  e.start_ns * 1e-9, e.duration_ns * 1e-9))
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(HOST_SPAN_PREFIX):
                        tr.spans.append((e.name, e.start_ns * 1e-9,
                                         e.duration_ns * 1e-9))
    return tr


def events_from_json(path: str) -> Trace:
    """The recorded form the tests keep: {"devices": {"0": [[name,
    category, detail, start, dur], ..]}, "spans": [[name, start, dur]]}."""
    with open(path) as f:
        raw = json.load(f)
    tr = Trace()
    for k, ops in raw["devices"].items():
        tr.devices[int(k)] = [Op(*o) for o in ops]
    tr.spans = [tuple(s) for s in raw.get("spans", [])]
    return tr


def latest_xplane(log_dir: str) -> str | None:
    hits = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                     recursive=True)
    return max(hits, key=os.path.getmtime) if hits else None


def union_intervals(intervals) -> list:
    """Sorted, merged [start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def clip(intervals, lo: float, hi: float) -> list:
    return [[max(s, lo), min(e, hi)] for s, e in intervals
            if min(e, hi) > max(s, lo)]


def busy_seconds(ops, lo: float, hi: float) -> float:
    """Seconds inside [lo, hi] in which at least one op ran."""
    merged = clip(union_intervals((o.start, o.start + o.dur) for o in ops),
                  lo, hi)
    return sum(e - s for s, e in merged)


def idle_gaps(ops, lo: float, hi: float) -> list:
    """The [start, end) intervals inside [lo, hi] in which no op ran."""
    merged = clip(union_intervals((o.start, o.start + o.dur) for o in ops),
                  lo, hi)
    gaps, at = [], lo
    for s, e in merged:
        if s > at:
            gaps.append([at, s])
        at = max(at, e)
    if hi > at:
        gaps.append([at, hi])
    return gaps


def attribute_gaps(gaps, spans) -> dict:
    """Seconds of idle time by the host span that covers it. Every instant
    of a gap goes to one span: the innermost (latest started) ``bench.*``
    span open then, or to ``other``."""
    out: dict = {}
    spans = sorted(spans, key=lambda s: s[1])
    for g0, g1 in gaps:
        cuts = {g0, g1}
        for _, s, d in spans:
            for t in (s, s + d):
                if g0 < t < g1:
                    cuts.add(t)
        pts = sorted(cuts)
        for a, b in zip(pts[:-1], pts[1:]):
            mid = 0.5 * (a + b)
            owner = "other"
            for name, s, d in spans:           # the latest started wins
                if s <= mid < s + d:
                    owner = name
            out[owner] = out.get(owner, 0.0) + (b - a)
    return out


def window_of(tr: Trace, name: str = "bench.window"):
    """[lo, hi] of the traced window: the host span ``bench.window`` if it
    was recorded, else first op start to last op end."""
    for n, s, d in tr.spans:
        if n == name:
            return s, s + d
    ops = [o for v in tr.devices.values() for o in v]
    if not ops:
        return 0.0, 0.0
    return (min(o.start for o in ops),
            max(o.start + o.dur for o in ops))


def time_by_label(ops, lo: float, hi: float) -> dict:
    out: dict = {}
    for o in ops:
        s, e = max(o.start, lo), min(o.start + o.dur, hi)
        if e > s:
            out[o.label] = out.get(o.label, 0.0) + (e - s)
    return out


def seconds_matching(ops, lo: float, hi: float, pred) -> float:
    """Union seconds of the ops that ``pred(op)`` accepts."""
    return busy_seconds([o for o in ops if pred(o)], lo, hi)


def summarize(tr: Trace) -> dict:
    """What the result line needs: window, busy seconds averaged over the
    devices, the top operations and the longest idle gaps by host span."""
    lo, hi = window_of(tr)
    n = max(len(tr.devices), 1)
    busy = sum(busy_seconds(ops, lo, hi) for ops in tr.devices.values()) / n
    by_label: dict = {}
    by_span: dict = {}
    for ops in tr.devices.values():
        for k, v in time_by_label(ops, lo, hi).items():
            by_label[k] = by_label.get(k, 0.0) + v / n
        for k, v in attribute_gaps(idle_gaps(ops, lo, hi),
                                   [s for s in tr.spans
                                    if s[0] != "bench.window"]).items():
            by_span[k] = by_span.get(k, 0.0) + v / n
    top = lambda d: [[k, v] for k, v in                      # noqa: E731
                     sorted(d.items(), key=lambda kv: -kv[1])[:10]]
    return {"window_s": hi - lo, "busy_s": busy, "lo": lo, "hi": hi,
            "device_ops": top(by_label), "idle_gaps": top(by_span)}
