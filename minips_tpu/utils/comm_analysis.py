"""Collective-traffic accounting from compiled HLO.

VERDICT round-1 task 6 asks for proof that the row-sharded embedding
pull/push does NOT degrade to "all-gather the table": communicated bytes
must scale with the *touched rows* (batch), never with table capacity
(SURVEY.md §7.4.2 "sparse push/pull at 1M samples/sec"). The reference has
the same sparsity property structurally — its Mailbox ships only the
key/val slices for one batch (SURVEY.md §3.3) — so this is a parity
invariant, not just a perf nicety.

This module extracts every cross-device collective from a compiled
executable's HLO and sums the bytes each moves, so tests and benches can
assert the invariant mechanically: compile the same pull/push at two table
sizes and require identical collective traffic; grow the batch and require
proportional growth (tests/test_sharded_traffic.py).

Parsing compiled HLO text is deliberate: post-SPMD-partitioning HLO is the
ground truth of what XLA actually scheduled on the interconnect, whereas
the traced jaxpr only shows what we *asked* for.
"""

from __future__ import annotations

import re
from dataclasses import dataclass

_COLLECTIVES = (
    "all-gather",
    "all-reduce",
    "all-to-all",
    "reduce-scatter",
    "collective-permute",
)

# HLO primitive-type → bytes per element. Sub-byte types (u4/s4, fp8) round
# up to 1; anything not listed falls back to a conservative 8 bytes with a
# warning (overestimating keeps the "traffic is small" guards sound) rather
# than crashing on newer-hardware HLO.
_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s4": 1, "u4": 1,
    "f8e5m2": 1, "f8e4m3": 1, "f8e4m3fn": 1, "f8e4m3b11fnuz": 1,
    "f8e5m2fnuz": 1, "f8e4m3fnuz": 1, "f8e3m4": 1, "f4e2m1fn": 1,
    "f8e8m0fnu": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

# e.g.:  %all-reduce.1 = f32[1024,64]{1,0} all-reduce(%fusion), ...
#        %ag = (s32[8]{0}, s32[8]{0}) all-gather(...)   (tuple results;
#        the chip's layouts hold parentheses: f32[8]{0:T(1024)S(1)})
_OP_RE = re.compile(
    r"(?:%?(?P<name>[\w.\-]+)\s*)?=\s*(?P<result>\(.*?\)|\S+?)\s+"
    r"(?P<op>" + "|".join(_COLLECTIVES) + r")(?:-start|-done)?\(")
# full HLO primitive-type names (f8e4m3fn, bf16, u4, ...): letters and
# digits interleave, so the name is letter-led alphanumeric — anchored by
# the [dims] bracket that only type names carry in shape position
_SHAPE_RE = re.compile(r"(?P<dtype>[a-z][a-z0-9]*)\[(?P<dims>[\d,]*)\]")


@dataclass(frozen=True)
class CollectiveOp:
    """One cross-device collective in compiled HLO."""
    kind: str      # all-gather / all-reduce / ...
    shape: str     # e.g. "f32[1024,64]"
    bytes: int     # payload size of the result
    # parsed result dims, one tuple per array in the (possibly tuple-)
    # result — guards compare these as INTEGERS (substring matching on
    # `shape` false-positives, e.g. 16384 inside f32[163840])
    dims: tuple = ()
    name: str = ""     # the instruction's own, e.g. "all-reduce.99"

    def has_dim(self, n: int) -> bool:
        return any(n in d for d in self.dims)


def _shape_bytes(shape_text: str, largest: bool = False):
    """(bytes, shapes, dims) across every array shape in ``shape_text``;
    ``largest=True`` returns only the biggest element's bytes (async
    ``-start`` tuples alias the operand next to the output)."""
    sizes, shapes, dims = [], [], []
    for m in _SHAPE_RE.finditer(shape_text):
        dt = m.group("dtype")
        if dt == "token":  # control-dependency tokens carry no payload
            continue
        n = 1
        for d in m.group("dims").split(","):
            if d:
                n *= int(d)
        per_elem = _DTYPE_BYTES.get(dt)
        if per_elem is None:
            import warnings
            warnings.warn(f"unknown HLO primitive type {dt!r}; assuming "
                          "16 bytes/element (conservative)", stacklevel=3)
            per_elem = 16  # >= the widest known type (c128)
        sizes.append(n * per_elem)
        shapes.append(f"{dt}[{m.group('dims')}]")
        dims.append(tuple(int(d) for d in m.group("dims").split(",") if d))
    total = (max(sizes) if largest else sum(sizes)) if sizes else 0
    return total, shapes, tuple(dims)


def collective_ops(hlo_text: str) -> list[CollectiveOp]:
    """All cross-device collectives in (post-partitioning) HLO text.

    ``bytes`` is the per-device result payload — the quantity that rides
    the interconnect once per device. Async ``-start``/``-done`` pairs are
    counted once, on the ``-start`` line. An async ``-start`` result is a
    TUPLE that aliases the operand alongside the output (e.g.
    ``(f32[512,32], f32[4096,32]) all-gather-start`` — operand, output —
    and ``collective-permute-start`` adds u32[] context scratch), so
    summing the tuple would double-count the payload: for ``-start`` ops
    we take the LARGEST element (the output; for permute in/out are the
    same shape, so either is the single payload). Sync variadic
    collectives (tuple-result ``all-reduce`` over several operands) do
    move every element, so those still sum.
    """
    ops: list[CollectiveOp] = []
    for line in hlo_text.splitlines():
        # a compiled step's text is megabytes of long lines: the pattern
        # is tried only on those that name a collective at all
        if not any(c in line for c in _COLLECTIVES):
            continue
        m = _OP_RE.search(line)
        if m is None or f"{m.group('op')}-done(" in line:
            continue
        is_start = f"{m.group('op')}-start(" in line
        nbytes, shapes, dims = _shape_bytes(m.group("result"),
                                            largest=is_start)
        ops.append(CollectiveOp(m.group("op"), " ".join(shapes), nbytes,
                                dims, m.group("name") or ""))
    return ops


def collective_bytes(compiled) -> int:
    """Total collective payload bytes per device for a compiled executable
    (the output of ``jax.jit(f).lower(*args).compile()``)."""
    return sum(op.bytes for op in collective_ops(compiled.as_text()))


def traffic_report(compiled) -> dict:
    """{total_bytes, ops:[{kind, shape, bytes}...]} — JSONL-friendly, for
    bench output and metrics (SURVEY.md §5.5)."""
    ops = collective_ops(compiled.as_text())
    return {
        "total_bytes": sum(o.bytes for o in ops),
        "ops": [{"kind": o.kind, "shape": o.shape, "bytes": o.bytes}
                for o in ops],
    }
