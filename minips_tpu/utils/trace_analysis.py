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
protobuf wire format for that one map. A CPU trace carries no such stat,
and the compiler's own instructions carry none on any backend: where
``programs.json`` lies in ``log_dir`` (``TrainLoop(profile_dir=)`` writes
the account the step keeps of its own program there:
``dump_programs``), such an op takes its phase from the account
by its instruction's name (``instruction_phases``: by scope, else by its
neighbours), and the step's memory by the compiler's count and the
collectives the compiler built under each PS phase are printed with the
rest. Without the file such ops are reported unnamed.

  python -m minips_tpu.utils.trace_analysis <log_dir> [--top N]
"""

from __future__ import annotations

import bisect
import glob
import json
import os
import re
from dataclasses import dataclass, field
from typing import Iterable, NamedTuple, Optional

from minips_tpu.utils import comm_analysis
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


def ps_phase_of(scope: str) -> Optional[str]:
    """The PS phase an op_name path lies in: the OUTERMOST of ``ps.pull``,
    ``ps.grad``, ``ps.push``, ``ps.update`` in it (``ps.push.dense`` and
    ``ps.push.sparse/<table>`` are ``ps.push``), or None."""
    for hit in _PHASE.findall(scope):
        for ps in prof.PS_PHASES:
            if hit == ps or hit.startswith(ps + "."):
                return ps
    return None


# ---------------------------------------------- a compiled program's phases
class Instruction(NamedTuple):
    """Where one instruction of a compiled step belongs."""
    ps_phase: Optional[str]    # ps.pull | ps.grad | ps.push | ps.update
    phase: Optional[str]       # the innermost named phase (``phase_of``)
    part: str                  # fwd | bwd | remat
    how: Optional[str]         # "scope", "neighbours", or None: not found


_HLO_INSTRUCTION = re.compile(r"^\s+(?:ROOT )?%([\w.\-]+) = (.*)$")
_HLO_OPCODE = re.compile(r" ([a-z][a-z0-9\-]*)\(")
_HLO_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="((?:[^"\\]|\\.)*)"')
_HLO_OPERAND = re.compile(r"%([\w.\-]+)")
_HLO_CALLS = re.compile(r"\bcalls=%([\w.\-]+)")
_HLO_SUBS = re.compile(r"\b(?:calls|body|condition|to_apply)=%([\w.\-]+)")
_HLO_PARAMETER = re.compile(r" parameter\((\d+)\)")


class _Hlo(NamedTuple):
    """One instruction line of a compiled module's text."""
    opcode: str
    operands: list             # instruction names, in order
    scope: Optional[str]       # its op_name
    calls: Optional[str]       # the computation a fusion calls
    parameter: Optional[int]   # a parameter's number
    subs: tuple                # every computation it calls (a loop's too)


def _computations(hlo_text: str) -> Iterable[tuple[str, dict]]:
    """(name, ``{instruction name: _Hlo}``) of every computation of a
    compiled module's text as jax prints it (names with ``%``), in the
    text's order: callees before callers, instructions as scheduled."""
    name, body = None, {}
    for line in hlo_text.splitlines():
        if line.endswith("{") and not line.startswith(" "):
            head = line.split("(", 1)[0].split()
            name, body = head[-1].lstrip("%") if head else "", {}
            continue
        if line == "}":
            if name is not None:
                yield name, body
            name = None
            continue
        m = _HLO_INSTRUCTION.match(line) if name is not None else None
        if m is None:
            continue
        rest = m.group(2)
        op = _HLO_OPCODE.search(rest)
        opcode, operands = (op.group(1) if op else ""), []
        if op:
            depth, i = 1, op.end()
            while i < len(rest) and depth:
                depth += (rest[i] == "(") - (rest[i] == ")")
                i += 1
            operands = _HLO_OPERAND.findall(rest[op.end():i])
        scope = _HLO_OP_NAME.search(rest)
        calls = _HLO_CALLS.search(rest) if opcode == "fusion" else None
        number = (_HLO_PARAMETER.search(rest) if opcode == "parameter"
                  else None)
        body[m.group(1)] = _Hlo(
            opcode, operands, scope.group(1) if scope else None,
            calls.group(1) if calls else None,
            int(number.group(1)) if number else None,
            tuple(_HLO_SUBS.findall(rest)))


def instruction_phases(hlo_text: str) -> dict[str, Instruction]:
    """``{instruction name: Instruction}`` for every instruction of a
    compiled module's text (``compiled.as_text()``): the names a device
    trace's op events carry.

    ``scope``: the instruction's own ``op_name`` lies in a PS phase
    (``ps_phase_of``). ``neighbours``: it does not (the compiler's own
    instructions carry no ``op_name``, the partitioner's one without a
    phase: the cast of the pulled vector, the push's all-reduce, copies
    between memories), and the dataflow leaves one choice. An instruction
    cannot run before the LATEST phase among its operands, and is made for
    the EARLIEST phase among its users; where the two are one phase it
    takes it, between two phases it stays without; where it has no operand
    in a phase (it reads the step's arguments) it runs with its first
    user, where no user, with its last operand. Operands and users without
    a phase of their own are looked through to theirs, and a user that is
    a fusion is looked INTO: what reads the value there is the fused
    instruction on that parameter, which has kept its own ``op_name`` (the
    slice of the pulled vector fused into the matmul that uses it is still
    ``ps.pull/split``)."""
    order = {p: i for i, p in enumerate(prof.PS_PHASES)}
    out: dict[str, Instruction] = {}
    readers: dict[str, dict] = {}    # computation -> {parameter no: phases}
    for comp, body in _computations(hlo_text):
        own = {n: ps_phase_of(v.scope) if v.scope else None
               for n, v in body.items()}
        users: dict[str, list] = {n: [] for n in body}
        for n, v in body.items():
            for k, o in enumerate(v.operands):
                if o in users:
                    users[o].append((n, k))
        before: dict[str, frozenset] = {}    # phases that feed n
        for n, v in body.items():
            before[n] = frozenset().union(*(
                (own[o],) if own[o] else before[o]
                for o in v.operands if o in before))
        after: dict[str, frozenset] = {}     # phases that read n
        for n in reversed(body):
            got = set()
            for u, k in users[n]:
                inside = readers.get(body[u].calls, {}).get(k)
                if inside:
                    got |= inside
                elif own[u]:
                    got.add(own[u])
                else:
                    got |= after[u]
            after[n] = frozenset(got)
        readers[comp] = {v.parameter: after[n] for n, v in body.items()
                         if v.parameter is not None}
        for n, v in body.items():
            phase, part = phase_of(v.scope or "")
            if own[n]:
                out[n] = Instruction(own[n], phase, part, "scope")
                continue
            last = max(before[n], key=order.get, default=None)
            first = min(after[n], key=order.get, default=None)
            if last is None or first is None:
                placed = last or first
            else:
                placed = last if last == first else None
            out[n] = Instruction(placed, phase or placed, part,
                                 "neighbours" if placed else None)
    return out


_ASYNC_START = re.compile(
    r"(async-collective|all-gather|all-reduce|reduce-scatter|all-to-all|"
    r"collective-permute)-start\b")
_ASYNC_DONE = re.compile(
    r"(async-collective|all-gather|all-reduce|reduce-scatter|all-to-all|"
    r"collective-permute)-done\b")
# what the schedule places between a start and its done that is no work
_NO_WORK = {"get-tuple-element", "tuple", "bitcast", "parameter", "constant",
            "copy-start", "copy-done", "partition-id", "replica-id",
            "after-all", ""}


def collective_overlap(hlo_text: str) -> dict[str, tuple[bool, int]]:
    """``{collective instruction name: (asynchronous, instructions
    between)}`` for every collective of a compiled module's text
    (``comm_analysis.collective_ops``' names). A collective is
    asynchronous where the entry computation starts it in one instruction
    and waits for it in another: an ``*-start`` / ``*-done`` pair, or the
    TPU's async collective fusions (``async-collective-start``, the
    fusions that carry it on beside compute, ``async-collective-done``).
    The count is of the instructions the schedule places between the
    start and the done that do work (fusions, custom calls, the carrying
    fusions themselves), and not the tuple plumbing, copies or another
    collective's start or done: what can run while the collective moves.
    A collective the entry runs in one instruction reads ``(False, 0)``."""
    comps = list(_computations(hlo_text))
    if not comps:
        return {}
    names = {op.name for op in comm_analysis.collective_ops(hlo_text)}
    within: dict[str, set] = {}     # computation -> collectives inside it
    for comp, body in comps:        # callees come before their callers
        got = set()
        for n, v in body.items():
            if n in names:
                got.add(n)
            got = got.union(*(within.get(s, ()) for s in v.subs))
        within[comp] = got
    entry = comps[-1][1]
    order = {n: i for i, n in enumerate(entry)}
    origin: dict[str, str] = {}     # an entry instruction -> its start
    done: dict[str, str] = {}       # a start -> its done
    for n, v in entry.items():
        if _ASYNC_START.match(n):
            origin[n] = n
            continue
        # the chain runs through the start's tuple and the fusions (and
        # loops) that carry the collective on; what merely reads a value
        # one of them made (a matmul's output beside the gathered part) is
        # not in it
        if not (v.opcode in ("get-tuple-element", "tuple")
                or _ASYNC_DONE.match(n)
                or any(within.get(s) for s in v.subs)):
            continue
        o = next((origin[o] for o in v.operands if o in origin
                  and not _ASYNC_DONE.match(o)), None)
        if o is not None:
            origin[n] = o
            if _ASYNC_DONE.match(n):
                done[o] = n
    between = {
        s: sum(1 for n in list(entry)[order[s] + 1:order[d]]
               if entry[n].opcode not in _NO_WORK
               and not _ASYNC_START.match(n) and not _ASYNC_DONE.match(n))
        for s, d in done.items()}
    out: dict[str, tuple[bool, int]] = {}
    for n, v in entry.items():
        inside = ({n} & names).union(*(within.get(s, ()) for s in v.subs))
        s = origin.get(n)
        for c in inside:
            out[c] = (True, between[s]) if s in between else (False, 0)
    return out


def account(text: str, memory: Optional[dict] = None) -> dict:
    """What ``programs.json`` holds of one program, read from its compiled
    text (``profiling.Program.text()``; ``memory`` is its ``memory``):
    ``instructions`` (``instruction_phases``, each a row of
    ``instruction_fields``) and ``collectives``, what the compiler BUILT
    for pull and push: every collective of the text
    (``comm_analysis.collective_ops``) as ``{"name", "kind", "shape",
    "bytes", "ps_phase", "async", "between"}``, the last two
    ``collective_overlap``'s: whether the schedule starts it apart from
    waiting for it, and how many instructions that do work it places in
    between."""
    known = instruction_phases(text)
    overlap = collective_overlap(text)
    return {"memory": memory, "text_bytes": len(text),
            "collectives": [
                {"name": op.name, "kind": op.kind, "shape": op.shape,
                 "bytes": op.bytes,
                 "ps_phase": getattr(known.get(op.name), "ps_phase", None),
                 "async": overlap.get(op.name, (False, 0))[0],
                 "between": overlap.get(op.name, (False, 0))[1]}
                for op in comm_analysis.collective_ops(text)],
            "instruction_fields": list(Instruction._fields),
            "instructions": {k: list(v) for k, v in known.items()}}


def accounts() -> dict:
    """``{program name: account(...)}`` of the programs this process's
    steps have staged (``profiling.programs()``); reads and parses each
    one's compiled text."""
    return {name: account(p.text(), p.memory)
            for name, p in prof.programs().items()}


def dump_programs(path: str) -> None:
    """Write ``accounts()`` as JSON: ``programs.json``."""
    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    with open(path, "w") as f:
        json.dump(accounts(), f)


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


def read_programs(log_dir: str) -> dict:
    """``programs.json`` of ``log_dir`` (``dump_programs``), or
    ``{}`` where there is none."""
    try:
        with open(os.path.join(log_dir, "programs.json")) as f:
            return json.load(f)
    except FileNotFoundError:
        return {}


def programs_summary(programs: dict) -> dict:
    """Of each program's account its memory and, under each PS phase, the
    collectives the compiler built: ``"all-gather bf16[4096] 8192 B
    (all-gather.4)"``."""
    out = {}
    for name, acc in programs.items():
        built: dict = {}
        for c in acc["collectives"]:
            built.setdefault(c["ps_phase"] or "no phase", []).append(
                f"{c['kind']} {c['shape']} {c['bytes']} B ({c['name']})"
                + (f" async, {c['between']} between"
                   if c.get("async") else ""))
        out[name] = {"memory": acc["memory"], "collectives": built}
    return out


def reduce(tr: Trace, top: int = 15, programs: Optional[dict] = None
           ) -> dict:
    """The numbers of the module's docstring from a ``Trace``;
    ``programs`` is ``read_programs``' (an op without a scope of its own
    is looked up there by its instruction's name)."""
    known = {}
    for acc in (programs or {}).values():
        at = {f: i for i, f in enumerate(acc["instruction_fields"])}
        for name, row in acc["instructions"].items():
            known[name] = (row[at["phase"]], row[at["part"]])
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
            phase, part = (phase_of(o.scope) if o.scope
                           else known.get(o.name, (None, "fwd")))
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
        "programs": programs_summary(programs or {}),
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
    out = reduce(read_xplane(path), top=top,
                 programs=read_programs(log_dir))
    out["trace_file"] = path
    return out


def main(argv: Optional[list[str]] = None) -> None:
    import argparse

    ap = argparse.ArgumentParser(
        description="Time by named phase, kernel, step and host span "
                    "from a captured profiler trace dir, with the step's "
                    "memory and collectives where programs.json lies "
                    "beside the trace")
    ap.add_argument("log_dir")
    ap.add_argument("--top", type=int, default=15)
    args = ap.parse_args(argv)
    try:
        print(json.dumps(summarize(args.log_dir, top=args.top), indent=2))
    except BrokenPipeError:  # e.g. piped into `head`
        os._exit(0)


if __name__ == "__main__":
    main()
