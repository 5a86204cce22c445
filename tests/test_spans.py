"""The one span type of the fused step (utils/profiling.py): what a span
records, where it goes, what it costs, and the host spans the program
itself opens."""

from __future__ import annotations

import glob
import json
import os
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from minips_tpu.utils import profiling as prof
from minips_tpu.utils.profiling import span


@pytest.fixture(autouse=True)
def fresh_ring():
    prof.clear()
    yield
    prof.clear()


def _named(name):
    return [s for s in prof.snapshot()[0] if s.name == name]


def test_span_records_name_times_parent_and_step():
    with span(prof.STEP) as outer:
        with span(prof.STEP_DISPATCH):
            pass
    (child,) = _named(prof.STEP_DISPATCH)
    (step,) = _named(prof.STEP)
    assert child.start_ns <= child.end_ns
    assert step.start_ns <= child.start_ns and child.end_ns <= step.end_ns
    assert child.parent == step.id and child.parent_name == prof.STEP
    assert step.parent is None and step.parent_name is None
    assert child.step == step.step == outer.step


def test_children_of_one_step_share_its_ordinal_and_steps_count_up():
    for _ in range(2):
        with span(prof.STEP):
            with span(prof.STEP_COLLECT):
                pass
            with span(prof.STEP_RESTORE):
                pass
    a, b = _named(prof.STEP)
    assert b.step == a.step + 1
    for s in _named(prof.STEP_COLLECT) + _named(prof.STEP_RESTORE):
        assert s.step == (a.step if s.parent == a.id else b.step)
    with span(prof.FEED):
        pass
    assert _named(prof.FEED)[0].step is None      # outside any ps.step


def test_self_time_of_a_parent_with_two_children():
    S = prof.Span
    spans = [S(1, None, "p", None, 0, 100, None),
             S(2, 1, "a", "p", 10, 30, None),
             S(3, 1, "b", "p", 50, 90, None),
             S(4, 3, "c", "b", 60, 70, None)]
    assert prof.self_time(spans) == {1: 40, 2: 20, 3: 30, 4: 10}
    # children that overlap cover their union, once
    spans = [S(1, None, "p", None, 0, 100, None),
             S(2, 1, "a", "p", 10, 60, None),
             S(3, 1, "b", "p", 40, 80, None)]
    assert prof.self_time(spans)[1] == 30


def test_exception_inside_a_span_still_closes_and_records_it():
    with pytest.raises(KeyError):
        with span("outer.x"):
            with span("inner.x"):
                raise KeyError("boom")
    assert [s.name for s in prof.snapshot()[0]] == ["inner.x", "outer.x"]
    with span("after.x"):
        pass
    assert _named("after.x")[0].parent is None    # the stack was unwound


def test_ring_is_bounded_and_counters_keep_what_it_dropped():
    n = prof.RING_SPANS + 50
    for _ in range(n):
        with span("many.x"):
            pass
    spans, counters = prof.snapshot()
    assert len(spans) == prof.RING_SPANS
    assert counters["many.x"][0] == n
    assert counters["many.x"][1] >= sum(s.end_ns - s.start_ns for s in spans)


def test_snapshot_is_a_copy():
    with span("one.x"):
        pass
    spans, counters = prof.snapshot()
    with span("two.x"):
        pass
    counters["one.x"] = (99, 99)
    assert [s.name for s in spans] == ["one.x"]
    assert [s.name for s in prof.snapshot()[0]] == ["one.x", "two.x"]
    assert prof.snapshot()[1]["one.x"][0] == 1


def test_span_decorates_a_function_one_span_a_call():
    @span("deco.x")
    def f(n):
        return f(n - 1) + 1 if n else 0

    assert f(2) == 2
    got = _named("deco.x")
    assert len(got) == 3
    assert sorted(s.parent is None for s in got) == [False, False, True]


def test_spans_of_two_threads_do_not_parent_each_other():
    inside = threading.Event()
    done = threading.Event()

    def other():
        with span("thread.x"):
            inside.set()
            done.wait(5)

    t = threading.Thread(target=other)
    t.start()
    assert inside.wait(5)
    with span("main.x"):
        pass
    done.set()
    t.join(5)
    assert not t.is_alive()
    assert _named("main.x")[0].parent is None
    assert _named("thread.x")[0].parent is None


def test_fresh_jit_inside_a_span_is_one_compile_under_that_span():
    x = jnp.arange(7.0) + 0.0               # made outside: its own programs
    salt = float(np.random.default_rng().integers(1 << 30))
    f = jax.jit(lambda v: v * salt + 1.0)   # a program no cache has seen
    prof.clear()
    with span("caller.x") as sp:
        f(x).block_until_ready()
    compiles = _named(prof.COMPILE)
    assert len(compiles) == 1
    assert compiles[0].parent == sp.id
    assert compiles[0].parent_name == "caller.x"
    assert compiles[0].end_ns > compiles[0].start_ns
    assert len(_named(prof.CACHE_MISS)) <= 1
    prof.clear()
    with span("caller.x"):
        f(x).block_until_ready()
    assert _named(prof.COMPILE) == []


def test_ps_step_events_reach_the_profilers_host_plane(tmp_path):
    from jax.profiler import ProfileData

    f = jax.jit(lambda v: (v @ v.T).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    with prof.profile_trace(str(tmp_path)):
        for _ in range(3):
            with span(prof.STEP):
                with span(prof.STEP_DISPATCH):
                    y = f(x)
            y.block_until_ready()
    (path,) = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                        recursive=True)
    host = ProfileData.from_file(path).find_plane_with_name("/host:CPU")
    events = [e for line in host.lines for e in line.events]
    steps = [e for e in events if e.name == prof.STEP]
    assert len(steps) == 3
    ring = _named(prof.STEP)
    assert [dict(e.stats)["step_num"] for e in steps] == [
        s.step for s in ring]
    assert sum(e.name == prof.STEP_DISPATCH for e in events) == 3


def test_an_empty_span_costs_microseconds():
    n = 20_000
    t0 = time.perf_counter()
    for _ in range(n):
        with span("cost.x"):
            pass
    per_span_us = 1e6 * (time.perf_counter() - t0) / n
    # measured 2.6 us here, 2.8 us on the chip machine's host (PERF.md);
    # the limit is generous for a loaded CI host
    assert per_span_us < 20.0, per_span_us


def test_dump_writes_spans_and_counters(tmp_path):
    with span(prof.STEP):
        with span(prof.STEP_DISPATCH):
            pass
    path = str(tmp_path / "spans.json")
    prof.dump(path)
    with open(path) as f:
        data = json.load(f)
    assert data["fields"] == list(prof.Span._fields)
    rows = [dict(zip(data["fields"], r)) for r in data["spans"]]
    assert [r["name"] for r in rows] == [prof.STEP_DISPATCH, prof.STEP]
    assert data["counters"][prof.STEP][0] == 1


# ---- the spans the program opens itself
def _lr_step(mesh):
    from minips_tpu.tables.dense import DenseTable

    table = DenseTable({"w": jnp.zeros(5)}, mesh, updater="sgd", lr=0.1)

    def grad_fn(p, b):
        def loss(p):
            return jnp.mean((b["x"] @ p["w"] - b["y"]) ** 2)
        return jax.value_and_grad(loss)(p)

    batch = {"x": jnp.ones((8, 5)), "y": jnp.ones(8)}
    return table, table.make_step(grad_fn), batch


def test_dense_table_init_and_step_inplace_open_their_spans(mesh4):
    table, step, batch = _lr_step(mesh4)
    (init,) = _named(prof.TABLE_INIT)
    under_init = [s for s in _named(prof.COMPILE) if s.parent == init.id]
    assert under_init                      # the jitted initialisers
    prof.clear()
    table.step_inplace(step, batch).block_until_ready()
    table.step_inplace(step, batch).block_until_ready()
    a, b = _named(prof.STEP)
    assert b.step == a.step + 1
    loads = _named(prof.COMPILE)
    assert loads and all(s.parent == a.id for s in loads)


def test_ps_train_step_records_collect_dispatch_restore_and_feed(mesh4):
    from minips_tpu.tables.sparse import SparseTable
    from minips_tpu.train.ps_step import PSTrainStep

    emb = SparseTable(64, 4, mesh4, name="emb", updater="adagrad")
    assert len(_named(prof.TABLE_INIT)) == 1
    ps = PSTrainStep(
        lambda dp, rows, b: jnp.mean((rows["emb"].sum(-1) - b["y"]) ** 2),
        sparse={"emb": emb}, key_fns={"emb": lambda b: b["ids"]})
    batch = ps.shard_batch({"ids": np.arange(8, dtype=np.int32),
                            "y": np.ones(8, np.float32)})
    assert len(_named(prof.FEED)) == 1
    prof.clear()
    ps(batch).block_until_ready()
    (step,) = _named(prof.STEP)
    kids = [s for s in prof.snapshot()[0] if s.parent == step.id]
    assert [k.name for k in kids if k.name != prof.COMPILE] == [
        prof.STEP_COLLECT, prof.STEP_DISPATCH, prof.STEP_RESTORE]
    assert all(k.step == step.step for k in kids)
    own = prof.self_time(prof.snapshot()[0])[step.id]
    assert 0 <= own <= step.end_ns - step.start_ns


def test_train_loop_spans_and_spans_json_beside_the_trace(tmp_path, mesh4):
    from minips_tpu.train.loop import TrainLoop

    table, step, batch = _lr_step(mesh4)
    prof.clear()
    d = str(tmp_path / "prof")
    loop = TrainLoop(lambda b: table.step_inplace(step, b),
                     [batch] * 4, log_every=2, profile_dir=d,
                     profile_range=(1, 3))
    assert len(loop.run(4)) == 4
    _, counters = prof.snapshot()
    assert counters[prof.LOOP_NEXT_BATCH][0] == 4
    assert counters[prof.LOOP_READBACK][0] == 4
    assert counters[prof.LOOP_LOG][0] == 2
    assert counters[prof.STEP][0] == 4
    with open(os.path.join(d, "spans.json")) as f:
        data = json.load(f)
    assert data["counters"][prof.STEP][0] == 4
    assert glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True)
