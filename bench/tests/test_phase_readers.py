"""The readers of the step's device time by PS phase and of the program's
own account (``ps_pull|grad|push|update|unscoped_ms_per_step``,
``ps_step_hbm``, ``ps_program_trace_s``): the join by instruction name and
the union on a small recorded trace of two chips and a recorded account
(``data/phase_trace.json``, ``data/phase_account.json``), what each reads
where there is nothing to read, and their entries in BENCHMARK.json."""

import io
import json
import os

import pytest

from benchlib import harness, phases, spec
from benchlib import trace as tracelib
from minips_tpu.utils import profiling as prof
from minips_tpu.utils import trace_analysis
from minips_tpu.utils.profiling import span

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
PHASE_METRICS = {"ps_pull_ms_per_step": "ps.pull",
                 "ps_grad_ms_per_step": "ps.grad",
                 "ps_push_ms_per_step": "ps.push",
                 "ps_update_ms_per_step": "ps.update",
                 "ps_unscoped_ms_per_step": phases.UNSCOPED}
ALL = list(PHASE_METRICS) + ["ps_step_hbm", "ps_program_trace_s"]
# what the two chips of the recorded trace read, ms of the window:
# chip 0 / chip 1, then their mean over the two traced steps
WANT_MS = {"ps_pull_ms_per_step": (14 + 18) / 2 / 2,
           "ps_grad_ms_per_step": (40 + 40) / 2 / 2,   # the while, once
           "ps_push_ms_per_step": (20 + 24) / 2 / 2,
           "ps_update_ms_per_step": (16 + 16) / 2 / 2,
           "ps_unscoped_ms_per_step": (6 + 6) / 2 / 2}


class Recorded:
    """A program as ``profiling.programs()`` hands it out: the readers
    here ask it for its memory alone."""

    def __init__(self, memory: dict):
        self.memory = memory


@pytest.fixture(autouse=True)
def fresh_ring():
    prof.clear()
    yield
    prof.clear()


@pytest.fixture()
def recorded(monkeypatch):
    with open(os.path.join(DATA, "phase_account.json")) as f:
        accs = json.load(f)
    monkeypatch.setattr(trace_analysis, "accounts", lambda: accs)
    monkeypatch.setattr(prof, "programs", lambda: {
        k: Recorded(v["memory"]) for k, v in accs.items()})
    return accs


def _run(tr=None, traced_steps=2):
    """A traced run of the recorded trace; ``tr`` None: a run whose trace
    holds no device op, as a CPU run's does."""
    tr = tr if tr is not None else tracelib.Trace()
    return harness.Run(trace=tr, trace_summary=tracelib.summarize(tr),
                       traced_steps=traced_steps, n_steps=3, window_s=1.0,
                       chips=len(tr.devices) or 1)


@pytest.fixture(scope="module")
def tr():
    return tracelib.events_from_json(os.path.join(DATA, "phase_trace.json"))


@pytest.mark.parametrize("metric", sorted(PHASE_METRICS))
def test_a_phase_is_the_union_of_its_ops_averaged_over_the_chips(
        metric, tr, recorded):
    """The join is by instruction name; ``while.3`` holds both runs of
    ``fusion.9`` and counts once; the chips' means; per traced step."""
    assert spec.load_reader(metric)(_run(tr)) == pytest.approx(
        WANT_MS[metric])


def test_an_op_the_account_does_not_know_is_unscoped(tr, recorded):
    """``fusion.77`` is in no account (4 ms over the two chips),
    ``copy.8`` in the account without a phase (2 ms): both are busy time
    that none of the four unions covers."""
    known = phases.by_instruction(phases.accounts())
    assert "fusion.77" not in known and known["copy.8"] is None
    t = tracelib.summarize(tr)
    got = phases.split(tr, t["lo"], t["hi"], known)
    assert got[phases.UNSCOPED] == pytest.approx(0.006)
    assert set(got) == set(phases.PS_PHASES) | {phases.UNSCOPED}


def test_the_five_sum_to_the_busy_time_where_nothing_overlaps(tr, recorded):
    run = _run(tr)
    five = sum(spec.load_reader(m)(run) for m in PHASE_METRICS)
    assert five == pytest.approx(spec.load_reader("device_ms_per_step")(run))
    assert five == pytest.approx((96 + 104) / 2 / 2)


def test_phases_that_overlap_exceed_their_share_by_what_is_hidden(recorded):
    """A collective under compute: the pull's all-gather runs while the
    gradient does; each union keeps its own time, their sum passes the
    busy time by the overlap, and ``unscoped`` stays what none covers."""
    ops = [tracelib.Op("all-gather.4", "all-gather", "", 1.000, 0.010),
           tracelib.Op("fusion.9", "fusion", "", 1.004, 0.010),
           tracelib.Op("fusion.77", "fusion", "", 1.020, 0.002)]
    got = phases.split(tracelib.Trace(devices={0: ops}), 1.0, 1.1,
                       phases.by_instruction(phases.accounts()))
    assert got["ps.pull"] == pytest.approx(0.010)
    assert got["ps.grad"] == pytest.approx(0.010)
    assert got[phases.UNSCOPED] == pytest.approx(0.002)
    assert sum(got.values()) - tracelib.busy_seconds(
        ops, 1.0, 1.1) == pytest.approx(0.006)


def test_ps_step_hbm_is_the_accounts_total_in_gb(recorded):
    assert spec.load_reader("ps_step_hbm")(_run()) == pytest.approx(
        12500 / 1e9)


def test_ps_program_trace_s_sums_trace_and_lower_records():
    """Under a ``ps.*`` span only; a function traced inside another's
    trace (``tanh`` here) has no record of its own, so nothing counts
    twice; a compilation is ``ps_program_load_s``'s."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    salt = float(np.random.default_rng().integers(1 << 30))
    f = jax.jit(lambda v: jnp.tanh(v) * salt)
    g = jax.jit(lambda v: v - salt)
    x = jnp.arange(5.0) + 0.0
    prof.clear()
    with span(prof.TABLE_INIT):
        f(x).block_until_ready()
    with span("bench.own_jit"):                 # the benchmark's own
        g(x).block_until_ready()
    mine = [s for s in prof.snapshot()[0]
            if s.name in (prof.TRACE, prof.LOWER)
            and s.parent_name == prof.TABLE_INIT]
    assert [(s.name, s.fun_name) for s in mine] == [
        (prof.TRACE, "<lambda>"), (prof.LOWER, "jit(<lambda>)")]
    assert spec.load_reader("ps_program_trace_s")(_run()) == pytest.approx(
        1e-9 * sum(s.end_ns - s.start_ns for s in mine))


@pytest.mark.parametrize("metric", ALL)
def test_a_reader_reports_nothing_on_a_cpu_run_without_an_account(metric):
    """No device op in the trace, no account, an empty ring."""
    assert spec.load_reader(metric)(_run()) is None


@pytest.mark.parametrize("metric", sorted(PHASE_METRICS))
def test_a_phase_reader_reports_nothing_without_a_device_trace(
        metric, recorded):
    """An account alone (a CPU run keeps one) is no device time."""
    assert spec.load_reader(metric)(_run()) is None
    untraced = harness.Run(trace=None, trace_summary=None, traced_steps=0)
    assert spec.load_reader(metric)(untraced) is None


@pytest.mark.parametrize("metric", sorted(PHASE_METRICS) + ["ps_step_hbm"])
def test_a_reader_reports_nothing_without_an_account(metric, tr):
    """A step that is not jitted keeps none; ``clear`` empties them."""
    assert prof.programs() == {}
    assert spec.load_reader(metric)(_run(tr)) is None


@pytest.mark.parametrize("metric", ALL)
def test_a_reader_reports_nothing_where_the_program_has_no_account(
        metric, tr, monkeypatch):
    """The parent commit's profiling module has no ``programs`` and no
    ``ps.trace`` / ``ps.lower`` names, its ``trace_analysis`` no
    ``accounts``: the reader returns None there and does not raise."""
    monkeypatch.delattr(prof, "programs")
    monkeypatch.delattr(trace_analysis, "accounts")
    monkeypatch.delattr(prof, "TRACE")
    monkeypatch.delattr(prof, "LOWER")
    with span(prof.TABLE_INIT):
        pass
    assert spec.load_reader(metric)(_run(tr)) is None


def test_the_seven_entries_are_the_last_of_per_layer():
    """They were the last when appended; the test keeps its name and now
    asserts their PLACES, ``per[19:26]`` after ``collective_ms_per_step``,
    so that entries appended after them may follow. In the issue's order,
    with its table's fields; no ``workloads`` list, so each is due in every
    cell that reports what it moves."""
    per = spec.load_benchmark()["per_layer"]
    rows = [(m["name"], m["unit"], m["better"], m["source"], m["layer"],
             m["moves"]) for m in per[19:26]]
    step = ("ms", "lower", "device_trace", "fused PS step",
            "samples_per_s_chip")
    assert rows == [
        ("ps_pull_ms_per_step",) + step, ("ps_grad_ms_per_step",) + step,
        ("ps_push_ms_per_step",) + step, ("ps_update_ms_per_step",) + step,
        ("ps_unscoped_ms_per_step",) + step,
        ("ps_step_hbm", "GB", "lower", "program_counter", "device",
         "samples_per_s_chip"),
        ("ps_program_trace_s", "s", "lower", "program_counter", "set-up",
         "setup_s")]
    assert not any("workloads" in m for m in per[19:26])
    assert [m["name"] for m in per[:5]] == [
        "input_ms_per_step", "step_ms_p50", "device_ms_per_step",
        "device_idle", "peak_hbm"]
    assert per[18]["name"] == "collective_ms_per_step"


@pytest.mark.parametrize("cell", [w["name"] for w in
                                  spec.load_benchmark()["workloads"]])
def test_all_seven_are_due_in_the_gpt2_xl_cells_and_in_no_other(cell):
    """Due in every cell: the readers read any fused step (every cell's
    system runs one), and a program that keeps no account reads ``None``
    and prints nothing. The name is the one the test had while the seven
    were due in the two ``gpt2-xl`` cells alone."""
    due = {m["name"] for m in spec.load_cell(cell).per_layer}
    assert set(ALL) <= due


def test_a_traced_tiny_run_prints_the_programs_own_two(tmp_path):
    """On the CPU the account and the stage records are there, a device
    trace is not: the two program counters print, the five phase metrics
    do not."""
    import tiny
    root = tiny.make_root(str(tmp_path), cells=["gpt2-xl.t1024-b4"])
    out = io.StringIO()
    rc = harness.run_cell("gpt2-xl.t1024-b4", 3700000123, 1.0, True,
                          require_tpu=False, root=root, out=out,
                          err=io.StringIO())
    assert rc == 0
    metrics = json.loads(out.getvalue().strip().splitlines()[-1])["metrics"]
    assert metrics["ps_step_hbm"]["unit"] == "GB"
    assert metrics["ps_step_hbm"]["value"] * 1e9 == pytest.approx(
        prof.programs()[prof.DENSE_STEP_FN].memory["total_bytes"])
    assert metrics["ps_program_trace_s"]["unit"] == "s"
    assert metrics["ps_program_trace_s"]["value"] > 0
    assert not set(PHASE_METRICS) & set(metrics)
