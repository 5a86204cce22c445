"""From a captured profiler trace to where the time went, by the program's
own names.

``summarize(log_dir)`` reads the newest ``.xplane.pb`` under ``log_dir``
(what ``profiling.profile_trace`` / ``TrainLoop(profile_dir=)`` write) and
reports, per device and averaged over the devices:

- busy time as the UNION of the intervals in which an op ran (overlapping
  ops, a ``while`` and its body, count once) and the idle share;
- time by named phase (``profiling.PHASES``: the ``jax.named_scope``s of
  the fused steps), union inside a phase, forward apart from backward and
  from the forward that remat runs again; what no phase names, by op
  (an unnamed ``while`` whose body is named is left with its own share);
- time and calls by kernel name (``profiling.KERNELS``);
- device time per step, from the ``ps.step`` step markers;
- every idle gap put down to the innermost ``ps.*`` / ``loop.*`` host span
  open in it, else to the innermost other annotation a caller wrote that
  is named like them (lowercase words joined by dots: ``bench.wait``),
  else to ``other``.

Planes, lines and events come from ``jax.profiler.ProfileData``. An op's
scope path (its HLO ``op_name``) is the ``tf_op`` stat of the event's
METADATA, which ``ProfileData`` does not hand out (an event's ``stats`` are
its own: offsets and durations), so ``read_metadata`` walks the file's
protobuf wire format for that one map. A CPU trace carries no such stat:
its ops are reported unnamed, from the host plane.

  python -m minips_tpu.utils.trace_analysis <log_dir> [--top N]
"""

from __future__ import annotations

import bisect
import glob
import json
import os
import re
from dataclasses import dataclass, field
from typing import Iterable, Optional

from minips_tpu.utils import profiling as prof

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)")
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
PROGRAM_SPAN = re.compile(r"^(ps|loop)\.")
CALLER_SPAN = re.compile(r"^[a-z][a-z0-9_]*(\.[a-z0-9_]+)+$")
_PHASE = re.compile(
    r"(?<![\w.])(" + "|".join(re.escape(p) for p in sorted(
        prof.PHASES, key=len, reverse=True)) + r")(?![\w.])")


@dataclass
class Op:
    name: str          # the HLO instruction: fusion.45, flash_fwd.26
    category: str      # the trace's hlo_category: "loop fusion", "custom-call"
    scope: str         # the op_name path; "" where the trace has none
    start: float       # seconds
    dur: float


@dataclass
class HostSpan:
    name: str
    start: float
    dur: float
    step: Optional[int] = None     # a ps.step marker's step_num


@dataclass
class Trace:
    devices: dict = field(default_factory=dict)    # device -> [Op]
    spans: list = field(default_factory=list)      # [HostSpan]
    source: str = "device"                         # "host": a CPU trace


# ----------------------------------------------------------- reading a file
def latest_xplane(log_dir: str) -> Optional[str]:
    """Newest ``*.xplane.pb`` under ``log_dir`` (any host, any run)."""
    hits = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                     recursive=True)
    return max(hits, key=os.path.getmtime) if hits else None


def _varint(buf, i: int) -> tuple[int, int]:
    x = shift = 0
    while True:
        c = buf[i]
        i += 1
        x |= (c & 0x7F) << shift
        shift += 7
        if c < 0x80:
            return x, i


def _fields(buf) -> Iterable[tuple[int, object]]:
    """(field number, value) of one protobuf message: an int for a varint,
    a memoryview for a length-delimited or fixed-width field."""
    i, n = 0, len(buf)
    while i < n:
        tag, i = _varint(buf, i)
        wire = tag & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            v, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            v, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"xplane: wire type {wire} at byte {i}")
        yield tag >> 3, v


def read_metadata(path: str) -> dict:
    """``{plane name: {event name: {stat name: text}}}`` from the event
    metadata of an ``.xplane.pb``, string-valued stats only (``tf_op``,
    ``hlo_category``). Field numbers are xplane.proto's: XSpace.planes 1;
    XPlane.name 2, event_metadata 4, stat_metadata 5 (maps: entry value 2);
    XEventMetadata.name 2, stats 5; XStatMetadata.id 1, name 2;
    XStat.metadata_id 1, str_value 5, ref_value 7."""
    with open(path, "rb") as f:
        raw = memoryview(f.read())
    out: dict = {}
    for num, plane in _fields(raw):
        if num != 1:
            continue
        name, events, stat_names = "", [], {}
        for pnum, v in _fields(plane):
            if pnum == 2:
                name = bytes(v).decode()
            elif pnum in (4, 5):
                entry = dict(_fields(v))
                if 2 not in entry:
                    continue
                if pnum == 5:
                    sm = dict(_fields(entry[2]))
                    stat_names[sm.get(1, 0)] = bytes(sm.get(2, b"")).decode()
                else:
                    events.append(entry[2])
        by_event = out.setdefault(name, {})
        for ev in events:
            ev_name, stats = "", {}
            for enum, v in _fields(ev):
                if enum == 2:
                    ev_name = bytes(v).decode()
                elif enum == 5:
                    st = dict(_fields(v))
                    key = stat_names.get(st.get(1, 0))
                    if 5 in st:
                        stats[key] = bytes(st[5]).decode(errors="replace")
                    elif 7 in st:
                        stats[key] = stat_names.get(st[7], "")
            by_event[ev_name] = stats
    return out


def _instruction(event_name: str) -> str:
    """libtpu names an op event by its whole HLO line, '%fusion.9 =
    f32[64,10]{..} fusion(...), kind=kLoop, ...': the instruction's name."""
    m = re.match(r"%?([\w.\-]+) = ", event_name)
    return m.group(1) if m else event_name


def read_xplane(path: str) -> Trace:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    meta = read_metadata(path)
    tr = Trace()
    host_ops: list = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            stats = meta.get(plane.name, {})
            ops = tr.devices.setdefault(m.group(1), [])
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for e in line.events:
                    s = stats.get(e.name, {})
                    ops.append(Op(_instruction(e.name),
                                  s.get("hlo_category", ""),
                                  s.get("tf_op", "").rstrip(":"),
                                  e.start_ns * 1e-9, e.duration_ns * 1e-9))
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    stats = dict(e.stats)
                    if "hlo_op" in stats:      # the CPU backend's thunks
                        host_ops.append(Op(
                            str(stats["hlo_op"]), "", "",
                            e.start_ns * 1e-9, e.duration_ns * 1e-9))
                    elif PROGRAM_SPAN.match(e.name) or CALLER_SPAN.match(
                            e.name):
                        step = (stats.get("step_num")
                                if e.name == prof.STEP else None)
                        tr.spans.append(HostSpan(
                            e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9,
                            None if step is None else int(step)))
    if not tr.devices and host_ops:
        tr.devices["cpu"], tr.source = host_ops, "host"
    return tr


# ------------------------------------------------------------ the reduction
def phase_of(scope: str) -> tuple[Optional[str], str]:
    """(the innermost named phase in an op_name path, under ``lm.mtp/``
    where the path lies in the prediction module, or None; which pass:
    ``fwd``, ``bwd`` (inside ``transpose(``) or ``remat`` (the forward run
    again for the backward, inside ``rematted_computation``))."""
    hits = _PHASE.findall(scope)
    part = ("remat" if "rematted_computation" in scope
            else "bwd" if "transpose(" in scope else "fwd")
    phase = hits[-1] if hits else None
    if prof.LM_MTP in hits and phase != prof.LM_MTP:
        # the prediction module runs a block and a head of its own: its
        # parts stay apart from the main model's (lm.mtp/lm.head)
        phase = f"{prof.LM_MTP}/{phase}"
    return phase, part


def kernel_of(op: Op) -> Optional[str]:
    """The kernel's name where ``op`` is a call of one of the program's
    Pallas kernels (``pl.pallas_call(name=...)`` names the instruction)."""
    base = re.sub(r"\.\d+$", "", op.name)
    return base if base in prof.KERNELS else None


def union(intervals: Iterable) -> list:
    """Sorted, merged [start, end] intervals."""
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def seconds_in(ops: Iterable[Op], lo: float, hi: float) -> float:
    """Seconds of [lo, hi] in which at least one of ``ops`` ran."""
    return sum(min(e, hi) - max(s, lo)
               for s, e in union((o.start, o.start + o.dur) for o in ops)
               if min(e, hi) > max(s, lo))


def uncovered(op: Op, merged: list) -> float:
    """Seconds of ``op`` outside the sorted, merged intervals."""
    s, e = op.start, op.start + op.dur
    left = e - s
    i = max(bisect.bisect_right(merged, [s, float("inf")]) - 1, 0)
    while i < len(merged) and merged[i][0] < e:
        left -= max(0.0, min(e, merged[i][1]) - max(s, merged[i][0]))
        i += 1
    return left


def idle_gaps(ops: Iterable[Op], lo: float, hi: float) -> list:
    """The [start, end] intervals of [lo, hi] in which no op ran."""
    gaps, at = [], lo
    for s, e in union((o.start, o.start + o.dur) for o in ops):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if s > at:
            gaps.append([at, s])
        at = max(at, e)
    if hi > at:
        gaps.append([at, hi])
    return gaps


def attribute_gaps(gaps: Iterable, spans: Iterable[HostSpan]) -> dict:
    """Idle seconds by host span. Every instant of a gap goes to one
    owner: the innermost (latest started) program span open then, else the
    innermost caller's span, else ``other``."""
    spans = sorted(spans, key=lambda s: s.start)
    cuts = sorted({t for s in spans for t in (s.start, s.start + s.dur)})
    out: dict = {}
    for g0, g1 in gaps:
        pts = [g0] + cuts[bisect.bisect_right(cuts, g0):
                          bisect.bisect_left(cuts, g1)] + [g1]
        for a, b in zip(pts[:-1], pts[1:]):
            mid, owner, own = 0.5 * (a + b), "other", False
            for s in spans:                    # the latest started wins
                if s.start > mid:
                    break
                if mid < s.start + s.dur:
                    mine = bool(PROGRAM_SPAN.match(s.name))
                    if mine or not own:
                        owner, own = s.name, mine
            out[owner] = out.get(owner, 0.0) + (b - a)
    return out


def reduce(tr: Trace, top: int = 15) -> dict:
    """The numbers of the module's docstring from a ``Trace``."""
    all_ops = [o for ops in tr.devices.values() for o in ops]
    if not all_ops:
        return {"error": "the trace holds no device op"}
    lo = min(o.start for o in all_ops)
    hi = max(o.start + o.dur for o in all_ops)
    n = len(tr.devices)
    busy = named = 0.0
    phases: dict = {}
    kernels: dict = {}
    unnamed: dict = {}
    gaps: dict = {}
    markers = sorted((s for s in tr.spans if s.step is not None),
                     key=lambda s: s.start)
    steps = [{"step": s.step, "host_ms": 1e3 * s.dur, "device_s": 0.0}
             for s in markers]
    for ops in tr.devices.values():
        busy += seconds_in(ops, lo, hi) / n
        by_phase: dict = {}
        loose = []          # what no phase names
        for o in ops:
            phase, part = phase_of(o.scope)
            if phase is None:
                loose.append(o)
            else:
                by_phase.setdefault((phase, part), []).append(o)
            k = kernel_of(o)
            if k is not None:
                row = kernels.setdefault(k, {"kernel": k, "calls": 0,
                                             "s": 0.0})
                row["calls"] += 1
                row["s"] += o.dur / n
        for key, members in by_phase.items():
            phases[key] = phases.get(key, 0.0) + seconds_in(
                members, lo, hi) / n
        covered = union((o.start, o.start + o.dur)
                        for m in by_phase.values() for o in m)
        named += sum(e - s for s, e in covered) / n
        for o in loose:     # listed with their time outside the phases
            left = uncovered(o, covered)
            if left > 0:
                row = unnamed.setdefault(
                    o.name, {"op": o.name, "category": o.category,
                             "scope": o.scope, "s": 0.0})
                row["s"] += left / n
        for k, v in attribute_gaps(idle_gaps(ops, lo, hi),
                                   tr.spans).items():
            gaps[k] = gaps.get(k, 0.0) + v / n
        for row, s, nxt in zip(steps, markers, markers[1:] + [None]):
            row["device_s"] += seconds_in(
                ops, s.start, hi if nxt is None else nxt.start) / n
    window = hi - lo
    idle = window - busy
    pct = lambda x, of: round(100.0 * x / of, 3) if of else 0.0  # noqa: E731
    by_time = lambda rows: sorted(rows, key=lambda r: -r["s"])   # noqa: E731
    calls_per = max(len(steps), 1) * n
    return {
        "source": tr.source, "devices": n,
        "window_s": window, "busy_s": busy,
        "idle_share_pct": pct(idle, window),
        "named_share_pct": pct(named, busy),
        "phases": by_time([
            {"phase": p, "part": part, "s": s, "pct_of_busy": pct(s, busy)}
            for (p, part), s in phases.items()]),
        "kernels": by_time([dict(r, calls_per_step=r["calls"] / calls_per)
                            for r in kernels.values()]),
        "unnamed_ops": by_time(unnamed.values())[:top],
        "steps": steps,
        "idle_gaps": by_time([
            {"span": k, "s": v, "pct_of_idle": pct(v, idle)}
            for k, v in gaps.items()]),
    }


def summarize(log_dir: str, *, top: int = 15) -> dict:
    """``reduce`` of the newest trace under ``log_dir``."""
    path = latest_xplane(log_dir)
    if path is None:
        return {"error": f"no *.xplane.pb under {log_dir}"}
    out = reduce(read_xplane(path), top=top)
    out["trace_file"] = path
    return out


def main(argv: Optional[list[str]] = None) -> None:
    import argparse

    ap = argparse.ArgumentParser(
        description="Time by named phase, kernel, step and host span "
                    "from a captured profiler trace dir")
    ap.add_argument("log_dir")
    ap.add_argument("--top", type=int, default=15)
    args = ap.parse_args(argv)
    try:
        print(json.dumps(summarize(args.log_dir, top=args.top), indent=2))
    except BrokenPipeError:  # e.g. piped into `head`
        os._exit(0)


if __name__ == "__main__":
    main()
