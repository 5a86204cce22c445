"""Profiling hooks (SURVEY.md §5.1): trace window produces an artifact;
spans accumulate host time by name (tests/test_spans.py has the rest)."""

from __future__ import annotations

import os
import time

import jax.numpy as jnp
import pytest

from minips_tpu.utils import profiling
from minips_tpu.utils.profiling import StepWindowProfiler, span


def test_step_window_profiler_writes_trace(tmp_path):
    d = str(tmp_path / "trace")
    p = StepWindowProfiler(d, start=2, stop=4)
    for i in range(6):
        p.on_step(i)
        jnp.sum(jnp.ones(16)).block_until_ready()
    p.close()
    # jax writes plugins/profile/<run>/ under the log dir
    found = [os.path.join(r, f) for r, _, fs in os.walk(d) for f in fs]
    assert found, "no trace artifacts written"


def test_window_closed_even_if_run_ends_early(tmp_path):
    p = StepWindowProfiler(str(tmp_path / "t2"), start=0, stop=100)
    p.on_step(0)
    p.close()  # must not raise / leak an open trace
    p.close()  # idempotent


def test_span_accumulates_count_and_total_by_name():
    profiling.clear()
    with span("phase_x"):
        time.sleep(0.01)
    with span("phase_x"):
        time.sleep(0.01)
    count, total_ns = profiling.snapshot()[1]["phase_x"]
    assert count == 2
    assert total_ns >= 0.02e9


# --------------------------------------- the account of a step's own program
def _dense_step(mesh, jit=True):
    """A tiny DenseTable over ``mesh`` with its fused step and a batch."""
    import jax
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from minips_tpu.parallel.mesh import DATA_AXIS
    from minips_tpu.tables.dense import DenseTable

    def grad_fn(p, b):
        def loss(p):
            return jnp.mean((b["x"] @ p["w"] + p["b"]) ** 2)
        return jax.value_and_grad(loss)(p)

    # values no other test's table holds: the programs are this test's own
    table = DenseTable({"w": jnp.full((37, 5), 0.37), "b": jnp.zeros((5,))},
                       mesh, updater="adam", lr=0.1)
    step = table.make_step(grad_fn, jit=jit)
    batch = {"x": jax.device_put(jnp.ones((8, 37)),
                                 NamedSharding(mesh, P(DATA_AXIS)))}
    return table, step, batch


def _under(step_span, names):
    return [s for s in profiling.snapshot()[0]
            if s.name in names and s.step == step_span.step]


def test_the_first_step_stages_its_program_once_and_names_it(mesh4):
    """Under the first ``ps.step``: exactly one ``ps.compile`` and one
    ``ps.lower``, and a ``ps.trace``, each named for ``ps_dense_step``
    (the call after the staging finds jax's caches: no second load);
    under the second: none."""
    table, step, batch = _dense_step(mesh4)
    profiling.clear()
    for _ in range(2):
        table.step_inplace(step, batch).block_until_ready()
    first, second = [s for s in profiling.snapshot()[0]
                     if s.name == profiling.STEP]
    stages = (profiling.TRACE, profiling.LOWER, profiling.COMPILE)
    mine = [s for s in _under(first, stages)
            if profiling.DENSE_STEP_FN in (s.fun_name or "")]
    assert sorted(s.name for s in mine if s.name != profiling.TRACE) == [
        profiling.COMPILE, profiling.LOWER]
    assert {s.fun_name for s in mine if s.name != profiling.TRACE} == {
        f"jit({profiling.DENSE_STEP_FN})"}
    # the staging's trace, and the call's, which finds it made (0 s); what
    # was traced INSIDE it (optax's jitted helpers) has no record
    traces = [s for s in _under(first, (profiling.TRACE,))]
    assert [s.fun_name for s in traces] == [profiling.DENSE_STEP_FN] * 2
    assert traces[1].end_ns - traces[1].start_ns < \
        0.1 * (traces[0].end_ns - traces[0].start_ns)
    assert all(s.parent_name == profiling.STEP for s in mine)
    # every compilation under the first step is the step's own
    assert [s.fun_name for s in _under(first, (profiling.COMPILE,))] == [
        f"jit({profiling.DENSE_STEP_FN})"]
    assert _under(second, stages) == []
    assert all(s.fun_name is None for s in (first, second))


def test_the_accounts_memory_is_the_compilers_own_count(mesh4):
    table, step, batch = _dense_step(mesh4)
    want = step.lower(*table.step_state,
                      batch).compile().memory_analysis()
    profiling.clear()
    table.step_inplace(step, batch)
    acc = profiling.programs()[profiling.DENSE_STEP_FN]
    assert acc.memory == {
        "argument_bytes": want.argument_size_in_bytes,
        "output_bytes": want.output_size_in_bytes,
        "alias_bytes": want.alias_size_in_bytes,
        "temp_bytes": want.temp_size_in_bytes,
        "code_bytes": want.generated_code_size_in_bytes,
        "total_bytes": (want.argument_size_in_bytes
                        + want.output_size_in_bytes
                        - want.alias_size_in_bytes
                        + want.temp_size_in_bytes
                        + want.generated_code_size_in_bytes)}
    assert acc.memory["alias_bytes"] > 0        # the donated state
    table.step_inplace(step, batch)             # kept, not made again
    assert profiling.programs()[profiling.DENSE_STEP_FN] is acc


def test_the_account_puts_every_phase_and_the_collectives_built(mesh4):
    """``trace_analysis.account`` of the kept text holds all four PS
    phases; the compiler built an all-gather for the pull and a reduce for
    the push, and the account says so under the names the compiled text
    gives them."""
    from minips_tpu.utils import trace_analysis

    table, step, batch = _dense_step(mesh4)
    profiling.clear()
    table.step_inplace(step, batch)
    acc = profiling.programs()[profiling.DENSE_STEP_FN]
    form = trace_analysis.accounts()[profiling.DENSE_STEP_FN]
    assert form["instruction_fields"] == ["ps_phase", "phase", "part", "how"]
    known = {k: trace_analysis.Instruction(*v)
             for k, v in form["instructions"].items()}
    assert {v.ps_phase for v in known.values()} >= set(profiling.PS_PHASES)
    assert {v.how for v in known.values()} >= {"scope", "neighbours"}
    built = {c["ps_phase"]: c for c in form["collectives"]}
    assert built[profiling.PULL]["kind"] == "all-gather"
    assert built[profiling.PUSH]["kind"] in ("reduce-scatter", "all-reduce")
    for c in built.values():
        assert c["name"] in known and c["bytes"] > 0
    assert form["memory"] == acc.memory
    assert form["text_bytes"] == len(acc.text()) > 0


@pytest.mark.parametrize("shards", [4, 1])
def test_the_ravel_of_the_gradients_is_the_pushs(shards):
    """On four shards the concatenate that makes the table's flat
    remainder of the workers' gradients (this table's leaves are all
    narrower than a lane tile, so all of them lie there, padded to shards
    that end on the chip's tile) lies under ``ps.push``, so the push reads
    the same work in every cell; on one shard, whose table stacks its
    leaves, the step makes no such vector at all."""
    import jax

    from minips_tpu.parallel.mesh import make_mesh
    from minips_tpu.utils.trace_analysis import ps_phase_of

    def eqns(jaxpr):
        for e in jaxpr.eqns:
            yield e
            for sub in jax.core.jaxprs_in_params(e.params):
                yield from eqns(sub)

    table, step, batch = _dense_step(
        make_mesh(shards, devices=jax.devices()[:shards]))
    jaxpr = jax.make_jaxpr(step)(*table.step_state, batch)
    flat = table._leaves.rest_padded if shards > 1 else 37 * 5 + 5
    ravels = [e for e in eqns(jaxpr.jaxpr)
              if e.primitive.name == "concatenate"
              and e.outvars[0].aval.shape == (flat,)]
    assert bool(ravels) == (shards > 1)
    assert {ps_phase_of(str(e.source_info.name_stack))
            for e in ravels} <= {profiling.PUSH}


def test_a_program_built_anew_is_staged_anew(mesh4):
    """A state that reaches the first call uncommitted and the second laid
    on the mesh makes jax build the step twice: the account of the first
    program turns stale with the second ``ps.compile``, and the third call
    keeps the account of the program that runs, from jax's caches, with no
    compilation of its own. ``make_step`` lays the state it is given on
    the mesh, so the test puts an uncommitted one in its place."""
    import jax

    from minips_tpu.tables.dense import DenseTable

    def grad_fn(p, b, seen):
        loss, g = jax.value_and_grad(
            lambda p: jnp.mean((b["x"] @ p["w"]) ** 2))(p)
        return loss, g, seen + 1.0

    table = DenseTable({"w": jnp.full((41, 3), 0.41)}, mesh4, lr=0.1)
    step = table.make_step(grad_fn, state=jnp.zeros(()))
    assert table.state.sharding == jax.NamedSharding(mesh4, jax.P())
    table.state = jnp.zeros(())
    batch = {"x": jax.device_put(jnp.ones((8, 41)), jax.NamedSharding(
        mesh4, jax.P(mesh4.axis_names[0])))}
    profiling.clear()
    table.step_inplace(step, batch)
    first = profiling.programs()[profiling.DENSE_STEP_FN]
    assert not profiling.stale(profiling.DENSE_STEP_FN)
    table.step_inplace(step, batch)
    assert first.stale and profiling.stale(profiling.DENSE_STEP_FN)
    table.step_inplace(step, batch)
    second = profiling.programs()[profiling.DENSE_STEP_FN]
    assert second is not first and not second.stale
    table.step_inplace(step, batch)
    assert profiling.programs()[profiling.DENSE_STEP_FN] is second
    compiles = [s.step for s in profiling.snapshot()[0]
                if s.name == profiling.COMPILE
                and s.fun_name == f"jit({profiling.DENSE_STEP_FN})"]
    steps = [s.step for s in profiling.snapshot()[0]
             if s.name == profiling.STEP]
    assert compiles == steps[:2]
    assert float(table.state) == 4.0


def test_a_step_that_is_not_jitted_keeps_no_account(mesh4):
    table, step, batch = _dense_step(mesh4, jit=False)
    profiling.clear()
    table.step_inplace(step, batch)
    assert profiling.programs() == {}


def test_the_fused_train_step_keeps_an_account_of_its_own(mesh4):
    from minips_tpu.tables.dense import DenseTable
    from minips_tpu.train.ps_step import PSTrainStep
    from minips_tpu.utils import trace_analysis

    table = DenseTable({"w": jnp.full((24,), 0.24)}, mesh4, lr=0.1)
    ps = PSTrainStep(lambda p, rows, b: jnp.mean((b["x"] @ p["w"]) ** 2),
                     dense=table)
    batch = ps.shard_batch({"x": jnp.ones((8, 24))})
    profiling.clear()
    for _ in range(2):
        ps(batch)
    acc = profiling.programs()
    assert list(acc) == [profiling.FUSED_STEP_FN]
    phases = {v.ps_phase for v in trace_analysis.instruction_phases(
        acc[profiling.FUSED_STEP_FN].text()).values()}
    # a dense table alone: its pull is the state handed in, no instruction
    assert {profiling.GRAD, profiling.PUSH} <= phases
    compiles = [s for s in profiling.snapshot()[0]
                if s.name == profiling.COMPILE
                and s.fun_name == f"jit({profiling.FUSED_STEP_FN})"]
    assert [s.parent_name for s in compiles] == [profiling.STEP_DISPATCH]


def test_clear_empties_the_accounts(mesh4):
    table, step, batch = _dense_step(mesh4)
    table.step_inplace(step, batch)
    assert profiling.DENSE_STEP_FN in profiling.programs()
    profiling.clear()
    assert profiling.programs() == {}


def test_what_set_up_recorded_outlives_any_number_of_step_spans():
    """Counters and stage events are kept where the step spans' ``maxlen``
    does not reach them: 9,000 steps after a ``ps.compile`` record it is
    still in ``snapshot()``, one tuple, oldest first."""
    import jax
    import numpy as np

    salt = float(np.random.default_rng().integers(1 << 30))
    x = jnp.arange(3.0)
    profiling.clear()
    with span(profiling.TABLE_INIT):
        jax.jit(lambda v: v * salt)(x).block_until_ready()
        profiling.counter(profiling.TABLE_PAD_KEYS, 12)
    for _ in range(9000):
        with span(profiling.STEP):
            pass
    spans, counters = profiling.snapshot()
    kept = [s for s in spans if s.name == profiling.COMPILE]
    assert kept and kept[0].parent_name == profiling.TABLE_INIT
    assert kept[0].fun_name == "jit(<lambda>)"
    assert [s.name for s in spans].count(profiling.TABLE_PAD_KEYS) == 1
    assert sum(s.name == profiling.STEP for s in spans) == \
        profiling.RING_SPANS
    assert counters[profiling.STEP][0] == 9000
    assert [s.end_ns for s in spans] == sorted(s.end_ns for s in spans)
    assert spans[0].name != profiling.STEP      # set-up's come first


def test_dump_writes_the_fun_name_and_the_programs_json(tmp_path, mesh4):
    import json

    from minips_tpu.train.loop import TrainLoop

    table, step, batch = _dense_step(mesh4)
    profiling.clear()
    d = str(tmp_path / "prof")
    TrainLoop(lambda b: table.step_inplace(step, b), [batch] * 3,
              profile_dir=d, profile_range=(1, 2)).run(3)
    with open(os.path.join(d, "spans.json")) as f:
        data = json.load(f)
    at = data["fields"].index("fun_name")
    name = data["fields"].index("name")
    assert data["fields"][-1] == "fun_name"
    named = {row[name]: row[at] for row in data["spans"]
             if row[at] is not None and profiling.DENSE_STEP_FN in row[at]}
    assert set(named) == {profiling.TRACE, profiling.LOWER,
                          profiling.COMPILE}
    with open(os.path.join(d, "programs.json")) as f:
        programs = json.load(f)
    acc = programs[profiling.DENSE_STEP_FN]
    assert acc["memory"]["total_bytes"] > 0
    assert {c["ps_phase"] for c in acc["collectives"]} >= {
        profiling.PULL, profiling.PUSH}
    assert acc["instructions"]
