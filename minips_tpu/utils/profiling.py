"""Spans, named phases and profiler windows for the fused PS step.

One span type. ``span(name)`` records name, start, end
(``time.perf_counter_ns``), the span that caused it (the innermost span
open on this thread) and the step it belongs to (the ordinal of the
enclosing ``ps.step``). Every span goes three places: a bounded in-memory
ring (always: a stall that shows only with no profiler running has to be
found there), per-name count and total, and the profiler's own trace when
a session is open (``jax.profiler.TraceAnnotation``;
``StepTraceAnnotation`` for ``ps.step``), so that host spans and device
ops share one clock. There is no switch: "off" is "no profiler session",
and the ring is the cost that is always paid.

Compilations are counted at the same boundaries: every program built or
read back from the persistent cache is a ``ps.compile`` record with its
duration, its parent span and its step, every function traced to a jaxpr
a ``ps.trace`` (the outermost: one traced inside another's trace is part
of the outer's seconds) and every module lowered a ``ps.lower``, each with
the program's name (jax's ``fun_name``); every cache miss a
``ps.cache_miss``. Counters and these records are kept apart from the step
spans, so that what set-up recorded is still there after any number of
steps.

A fused step keeps an account of its own program (``stage``, ``programs``):
the compiler's count of its memory and the compiled text, from which
``trace_analysis.account`` reads, when somebody asks, the PS phase of every
instruction and the collectives the compiler built.

The names below are the only definition of the host spans and of the
``jax.named_scope`` phases inside the jitted steps: call sites and the
reduction (``utils/trace_analysis.py``) import them from here.

``profile_trace`` / ``StepWindowProfiler`` capture the profiler's trace;
``TrainLoop(profile_dir=, profile_range=)`` is the operator's way to one,
and writes ``spans.json`` and ``programs.json`` beside it.
"""

from __future__ import annotations

import collections
import contextlib
import itertools
import json
import os
import threading
import time
from typing import Iterable, Iterator, NamedTuple, Optional

import jax
from jax import monitoring

# ---- host spans
STEP = "ps.step"
STEP_COLLECT = "ps.step.collect"
STEP_DISPATCH = "ps.step.dispatch"
STEP_RESTORE = "ps.step.restore"
FEED = "ps.feed"
TABLE_INIT = "ps.table.init"
LOOP_NEXT_BATCH = "loop.next_batch"
LOOP_PREFETCH = "loop.prefetch"
LOOP_READBACK = "loop.readback"
LOOP_LOG = "loop.log"
LOOP_CHECKPOINT = "loop.checkpoint"
# ---- counters, recorded in the ring like spans, under the span open then
COMPILE = "ps.compile"
CACHE_MISS = "ps.cache_miss"
# ---- the two stages before a compilation, counted like it
TRACE = "ps.trace"
LOWER = "ps.lower"
# ---- phases of the jitted steps (jax.named_scope -> an HLO op's op_name)
PULL = "ps.pull"
GRAD = "ps.grad"
PUSH = "ps.push"
PUSH_DENSE = "ps.push.dense"
PUSH_SPARSE = "ps.push.sparse"
UPDATE = "ps.update"
SPARSE_DEDUP = "sparse.dedup"
SPARSE_ADAGRAD_SORTED = "sparse.adagrad_sorted"
SPARSE_ADAGRAD_DENSE = "sparse.adagrad_dense"
SPARSE_ADAM_SORTED = "sparse.adam_sorted"
SPARSE_ADAM_DENSE = "sparse.adam_dense"
LM_EMBED = "lm.embed"
LM_ATTN = "lm.attn"
LM_MLP = "lm.mlp"
LM_HEAD = "lm.head"
# the ZAYA block (models/zaya.py): lm.attn.cca lies inside lm.attn, and
# lm.moe with its four parts stands where a dense block has lm.mlp
LM_ATTN_CCA = "lm.attn.cca"
LM_MOE = "lm.moe"
LM_MOE_ROUTER = "lm.moe.router"
LM_MOE_DISPATCH = "lm.moe.dispatch"
LM_MOE_EXPERTS = "lm.moe.experts"
LM_MOE_COMBINE = "lm.moe.combine"
# the latent-attention block (models/mla_moe.py): lm.attn.mla lies inside
# lm.attn (latent projections, their norms, rotary, k from k_nope and the
# shared k_rope), lm.moe.shared inside lm.moe, and lm.mtp is around the
# whole prediction module, so that the lm.head inside it (lm.mtp/lm.head)
# stays apart from the main head's
LM_ATTN_MLA = "lm.attn.mla"
LM_MOE_SHARED = "lm.moe.shared"
LM_MTP = "lm.mtp"
# the linear-attention block (models/olmo_hybrid.py): lm.linattn stands
# where a full-attention layer has lm.attn; inside it the six projections
# of the input and the output's, the three causal convolutions with their
# SiLU, the gated delta rule (ops/delta_rule.py: norms of q and k, decay
# and write strength, chunk terms, both scans) and the normed output gate
LM_LINATTN = "lm.linattn"
LM_LINATTN_PROJ = "lm.linattn.proj"
LM_LINATTN_CONV = "lm.linattn.conv"
LM_LINATTN_SCAN = "lm.linattn.scan"
LM_LINATTN_GATE = "lm.linattn.gate"
# ---- counters of the routing observer (zaya.routing_stats,
# mla_moe.routing_stats), per log line; the two losses where a model has
# a prediction module beside its main head
MOE_TOKENS_HELD = "moe.tokens_held"
MOE_LOAD_MAX_OVER_MEAN = "moe.load_max_over_mean"
LM_NLL = "lm.nll"
MTP_NLL = "mtp.nll"
# ---- counters of the linear-attention observer (olmo_hybrid.observe),
# per log line: over the layers, the smallest mean decay exp(g), the
# largest mean write strength beta (up to 2) and the largest entry of a
# final state (a state that grows is the first sign of a wrong sign or a
# missing norm)
LINATTN_DECAY_MEAN = "linattn.decay_mean"
LINATTN_BETA_MEAN = "linattn.beta_mean"
LINATTN_STATE_ABSMAX = "linattn.state_absmax"
# the window/full hybrid (models/laguna.py): lm.attn.window lies inside
# lm.attn around a window layer's attention, lm.attn.gate around the
# per-head output gate; its observer (laguna.routing_stats) logs lm.nll,
# the two routing counters above and, by layer kind, the gate's mean
# (attn.gate_mean.window, attn.gate_mean.full)
LM_ATTN_WINDOW = "lm.attn.window"
LM_ATTN_GATE = "lm.attn.gate"
ATTN_GATE_MEAN = "attn.gate_mean"
# ---- recorded once as a windowed flash kernel is built: of the (Q tile,
# K tile) pairs the causal kernel visits at the shape, the share it visits
FLASH_WIN_LIVE_SHARE = "flash.win_live_share"
# ---- under ps.table.init: the zero keys a DenseTable's range padding
# added (shards on a mesh end on the chip's tile, mesh.SHARD_TILE)
TABLE_PAD_KEYS = "ps.table.pad_keys"
# ---- under ps.table.init, a DenseTable on its leaves over several shards:
# the leaves sharded on a dimension of their own (``dense.shard_dim``), and
# the keys of the leaves that lie in its flat remainder instead
TABLE_SHARDED_LEAVES = "ps.table.sharded_leaves"
TABLE_FLAT_KEYS = "ps.table.flat_keys"
# ---- a DenseTable on its leaves made its key-space vector (``params``,
# ``opt_state``) from its leaves, under the span open then; value: 1
TABLE_VIEW = "ps.table.view"
# ---- a torn checkpoint step that restore() walked past; value: the step
CKPT_SKIP_TORN = "ckpt.skip_torn"
# ---- kernels (pl.pallas_call(name=...)) and the jitted steps' names
FLASH_FWD = "flash_fwd"
FLASH_BWD = "flash_bwd"
# the same kernels under a window (flash_attention(window=)): their grids
# walk the band alone
FLASH_FWD_WIN = "flash_fwd_win"
FLASH_BWD_WIN = "flash_bwd_win"
# ---- what flash_fwd leaves for the backward kernel, as checkpoint names
# (jax.ad_checkpoint.checkpoint_name): a block checkpoint whose policy
# saves both does not run flash_fwd a second time for the backward
FLASH_OUT = "flash_out"
FLASH_LSE = "flash_lse"
FLASH_RESIDUALS = (FLASH_OUT, FLASH_LSE)
# ---- the same for the gated delta rule's forward scan
# (ops/delta_rule.py): its output and the state at the start of every chunk
GDN_OUT = "gdn_out"
GDN_STATES = "gdn_states"
GDN_RESIDUALS = (GDN_OUT, GDN_STATES)
GATHER_ROWS = "gather_rows"
# XLA's own grouped-matmul kernel: what jax.lax.ragged_dot (the dropless
# expert layer's three products) lowers to on the TPU
RAGGED_DOT = "ragged-dot-none"
DENSE_STEP_FN = "ps_dense_step"
FUSED_STEP_FN = "ps_fused_step"

# an op_name path may hold several (ps.grad/lm.attn/...): a reduction
# takes the innermost
PS_PHASES = (PULL, GRAD, PUSH, UPDATE)      # in the order a step runs them
PHASES = (PULL, GRAD, PUSH, PUSH_DENSE, PUSH_SPARSE, UPDATE,
          SPARSE_DEDUP, SPARSE_ADAGRAD_SORTED, SPARSE_ADAGRAD_DENSE,
          SPARSE_ADAM_SORTED, SPARSE_ADAM_DENSE,
          LM_EMBED, LM_ATTN, LM_MLP, LM_HEAD, LM_ATTN_CCA, LM_MOE,
          LM_MOE_ROUTER, LM_MOE_DISPATCH, LM_MOE_EXPERTS, LM_MOE_COMBINE,
          LM_ATTN_MLA, LM_MOE_SHARED, LM_MTP, LM_LINATTN, LM_LINATTN_PROJ,
          LM_LINATTN_CONV, LM_LINATTN_SCAN, LM_LINATTN_GATE,
          LM_ATTN_WINDOW, LM_ATTN_GATE)
KERNELS = (FLASH_FWD, FLASH_BWD, GATHER_ROWS, RAGGED_DOT, FLASH_FWD_WIN,
           FLASH_BWD_WIN)

RING_SPANS = 8192
_STAGE_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": TRACE,
    "/jax/core/compile/jaxpr_to_mlir_module_duration": LOWER,
    "/jax/core/compile/backend_compile_duration": COMPILE,
}
_CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"


class Span(NamedTuple):
    """One closed span. ``parent`` is the id of the span that caused it
    (None at top level), ``step`` the ordinal of the enclosing
    ``ps.step`` (None outside one); times are ``perf_counter_ns``;
    ``fun_name`` is the program a ``ps.trace`` / ``ps.lower`` /
    ``ps.compile`` record is of (``jit(ps_dense_step)``), None for a
    span."""
    id: int
    parent: Optional[int]
    name: str
    parent_name: Optional[str]
    start_ns: int
    end_ns: int
    step: Optional[int]
    fun_name: Optional[str] = None


_ring: collections.deque = collections.deque(maxlen=RING_SPANS)
# counters and the stage events: one-off records, mostly of set-up, that
# the step spans of a long window must not push out
_marks: collections.deque = collections.deque(maxlen=RING_SPANS)
_counters: dict[str, list] = {}      # name -> [count, total_ns]
_programs: dict[str, "Program"] = {}
_lock = threading.Lock()     # guards the four above and _listening
_ids = itertools.count()
_steps = itertools.count()
_open = threading.local()            # .stack: the spans open on a thread
_listening = False


def _stack() -> list:
    try:
        return _open.stack
    except AttributeError:
        _open.stack = []
        return _open.stack


def _record(into: collections.deque, name: str, start_ns: int, end_ns: int,
            sid: int, parent: Optional["span"], step: Optional[int],
            fun_name: Optional[str] = None) -> None:
    rec = Span(sid, None if parent is None else parent.id, name,
               None if parent is None else parent.name,
               start_ns, end_ns, step, fun_name)
    with _lock:
        into.append(rec)
        c = _counters.get(name)
        if c is None:
            _counters[name] = [1, end_ns - start_ns]
        else:
            c[0] += 1
            c[1] += end_ns - start_ns


def _record_under_open_span(name: str, duration_ns: int,
                            fun_name: Optional[str] = None) -> None:
    """A counter's record, ending now, under the span open on this thread."""
    end = time.perf_counter_ns()
    stack = _stack()
    parent = stack[-1] if stack else None
    _record(_marks, name, end - duration_ns, end, next(_ids), parent,
            None if parent is None else parent.step, fun_name)


def counter(name: str, value: float) -> None:
    """A reading that is no time (tokens routed, a load ratio): a record
    of no duration in the ring, under the span open on this thread, and
    ``value`` added to the name's total, which ``snapshot`` hands out in
    place of nanoseconds."""
    _record_under_open_span(name, 0)
    with _lock:
        _counters[name][1] += value


def _on_duration(event: str, duration_secs: float, **kw) -> None:
    name = _STAGE_EVENTS.get(event)
    if name is None:
        return
    if name == TRACE and not jax.core.trace_ctx.is_top_level():
        # a function traced INSIDE another's trace (thousands a model) is
        # part of the outer one's seconds: only the outermost is recorded
        return
    fun_name = kw.get("fun_name")
    _record_under_open_span(name, int(duration_secs * 1e9), fun_name)
    if name == COMPILE and fun_name:
        # the program an account is kept of, compiled AGAIN (other
        # arguments: a state that was uncommitted on the first call and
        # lies on the mesh on the second): the account is of a program
        # that no longer runs, and the step's next call stages anew
        kept = _programs.get(fun_name[len("jit("):-1])
        if kept is not None:
            kept.stale = True


def _on_event(event: str, **kw) -> None:
    if event == _CACHE_MISS_EVENT:
        _record_under_open_span(CACHE_MISS, 0)


def _listen() -> None:
    global _listening
    with _lock:
        if _listening:
            return
        _listening = True
    monitoring.register_event_duration_secs_listener(_on_duration)
    monitoring.register_event_listener(_on_event)


class span(contextlib.ContextDecorator):
    """``with span(name):`` or ``@span(name)`` on a function — see the
    module's docstring. An exception inside still closes and records the
    span."""

    __slots__ = ("name", "id", "step", "_parent", "_t0", "_ann")

    def __init__(self, name: str):
        self.name = name

    def _recreate_cm(self) -> "span":
        return span(self.name)      # one object a call: spans nest

    def __enter__(self) -> "span":
        if not _listening:
            _listen()
        stack = _stack()
        self._parent = stack[-1] if stack else None
        self.id = next(_ids)
        if self.name == STEP:
            self.step = next(_steps)
            self._ann = jax.profiler.StepTraceAnnotation(
                STEP, step_num=self.step)
        else:
            self.step = (None if self._parent is None
                         else self._parent.step)
            self._ann = jax.profiler.TraceAnnotation(self.name)
        self._ann.__enter__()
        stack.append(self)
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> bool:
        t1 = time.perf_counter_ns()
        _stack().pop()
        _record(_ring, self.name, self._t0, t1, self.id, self._parent,
                self.step)
        self._ann.__exit__(*exc)
        return False


def snapshot() -> tuple[tuple, dict]:
    """A copy of what was recorded: (the spans the ring holds and the
    counters' and stage events' records, as one tuple, oldest first by
    when each ended; ``{name: (count, total_ns)}`` over every record since
    the start or the last ``clear``, also those since dropped)."""
    with _lock:
        recs = list(_ring) + list(_marks)
        counters = {k: (v[0], v[1]) for k, v in _counters.items()}
    recs.sort(key=lambda s: s.end_ns)
    return tuple(recs), counters


def clear() -> None:
    """Empty the ring, the counters and the programs' accounts (tests,
    tools); ids and step ordinals keep counting."""
    with _lock:
        _ring.clear()
        _marks.clear()
        _counters.clear()
        _programs.clear()


def self_time(spans: Iterable[Span]) -> dict[int, int]:
    """``{span id: ns}``: each span's duration minus the part of its
    interval that its child spans (among ``spans``) cover."""
    spans = list(spans)
    kids: dict[int, list] = collections.defaultdict(list)
    for s in spans:
        if s.parent is not None:
            kids[s.parent].append(s)
    out = {}
    for s in spans:
        covered, hi = 0, s.start_ns
        for k in sorted(kids.get(s.id, ()), key=lambda k: k.start_ns):
            lo = max(k.start_ns, hi)
            end = min(k.end_ns, s.end_ns)
            if end > lo:
                covered += end - lo
                hi = end
        out[s.id] = s.end_ns - s.start_ns - covered
    return out


def dump(path: str) -> None:
    """Write ``snapshot()`` as JSON: ``{"spans": [Span fields...],
    "fields": [...], "counters": {name: [count, total_ns]}}``."""
    spans, counters = snapshot()
    # a run that ended before its profiler window opened has no directory
    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    with open(path, "w") as f:
        json.dump({"fields": list(Span._fields),
                   "spans": [list(s) for s in spans],
                   "counters": {k: list(v) for k, v in counters.items()}},
                  f)


class Program:
    """The account a fused step keeps of its own program, from the one
    ``Compiled`` that ``stage`` made before the step's call.

    ``memory`` is the compiler's own count (``memory_analysis()``), bytes a
    chip: ``argument_bytes``, ``output_bytes``, ``alias_bytes`` (outputs
    that live in donated arguments), ``temp_bytes``, ``code_bytes`` and
    ``total_bytes``, what the step holds while it runs (arguments + outputs
    - aliases + temporaries + code); None where the backend counts nothing.
    ``text()`` is the compiled module's text, made when somebody first asks
    (a reader after the window, the operator's dump) and never in set-up:
    ``trace_analysis.account`` reads the phase of every instruction and the
    collectives the compiler built from it. ``stale`` turns True when the
    program is compiled again after this account was kept."""

    def __init__(self, name: str, compiled):
        self.name = name
        self.stale = False
        self._compiled = compiled
        self._text: Optional[str] = None
        m = compiled.memory_analysis()
        self.memory = None if m is None else {
            "argument_bytes": int(m.argument_size_in_bytes),
            "output_bytes": int(m.output_size_in_bytes),
            "alias_bytes": int(m.alias_size_in_bytes),
            "temp_bytes": int(m.temp_size_in_bytes),
            "code_bytes": int(m.generated_code_size_in_bytes),
            "total_bytes": int(
                m.argument_size_in_bytes + m.output_size_in_bytes
                - m.alias_size_in_bytes + m.temp_size_in_bytes
                + m.generated_code_size_in_bytes)}

    def text(self) -> str:
        """The compiled module's text. Once it is read the ``Compiled`` is
        let go, and with it the account's hold on the executable."""
        if self._text is None:
            self._text = self._compiled.as_text()
            self._compiled = None
        return self._text


def stage(name: str, step, *args) -> None:
    """Called before a call of a jitted ``step`` on ``args``, under the
    caller's ``ps.step``: the step's FIRST call, and the first after
    ``stale(name)``. Lowers and compiles the program here, where the call
    would have, and keeps its account under ``name``. The two share jax's
    caches either way round: the call that follows finds the executable
    (one ``ps.compile`` record a program, as before), and a program that a
    call has compiled already is staged without a record. A step that is
    not jitted has no ``lower`` and keeps no account."""
    lower = getattr(step, "lower", None)
    if lower is None:
        return
    program = Program(name, lower(*args).compile())
    with _lock:
        _programs[name] = program


def stale(name: str) -> bool:
    """True where the account kept under ``name`` is of a program that has
    been compiled again since (the listener saw a ``ps.compile`` of
    ``jit(<name>)`` after the account was kept): the steps run another
    executable than the account describes until the step is staged anew."""
    kept = _programs.get(name)
    return kept is not None and kept.stale


def programs() -> dict[str, Program]:
    """``{program name: its account}``: one for ``ps_dense_step``, one for
    ``ps_fused_step``, each of the step staged last under that name."""
    with _lock:
        return dict(_programs)


@contextlib.contextmanager
def profile_trace(log_dir: str) -> Iterator[None]:
    """Capture a jax.profiler trace into ``log_dir`` (view with
    TensorBoard's profile plugin, or reduce the ``.xplane.pb`` with
    ``python -m minips_tpu.utils.trace_analysis``). Whoever calls this
    asked for a trace: a profiler that cannot start or stop raises."""
    os.makedirs(log_dir, exist_ok=True)
    with jax.profiler.trace(log_dir):
        yield


class StepWindowProfiler:
    """Trace exactly the steps in [start, stop) — skipping compile-bearing
    early steps, the standard TPU profiling hygiene (first call traces +
    compiles and would drown the steady-state timeline)."""

    def __init__(self, log_dir: str, start: int, stop: int):
        if stop <= start:
            raise ValueError("profile window must be non-empty")
        self.log_dir = log_dir
        self.start = start
        self.stop = stop
        self._ctx: Optional[contextlib.AbstractContextManager] = None

    def on_step(self, step: int) -> None:
        """Call once per step with the 0-based step index (before work)."""
        if step == self.start and self._ctx is None:
            self._ctx = profile_trace(self.log_dir)
            self._ctx.__enter__()
        elif step == self.stop and self._ctx is not None:
            self._ctx.__exit__(None, None, None)
            self._ctx = None

    def close(self) -> None:
        if self._ctx is not None:
            self._ctx.__exit__(None, None, None)
            self._ctx = None
