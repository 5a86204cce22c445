"""The two readers of the program's own ring (``ps_host_ms_per_step``,
``ps_program_load_s``): on a ring filled by real spans, on an empty ring,
and their entries in BENCHMARK.json."""

import statistics
import time

import pytest

from benchlib import harness, spec
from minips_tpu.utils import profiling as prof
from minips_tpu.utils.profiling import span


@pytest.fixture(autouse=True)
def fresh_ring():
    prof.clear()
    yield
    prof.clear()


def _run():
    """A reader's argument; these two read the ring, not the run."""
    return harness.Run(n_steps=3, window_s=1.0, chips=1)


def test_ps_host_ms_per_step_is_the_median_ps_step_span():
    read = spec.load_reader("ps_host_ms_per_step")
    for pause in (0.002, 0.004, 0.030):
        with span(prof.STEP):
            with span(prof.STEP_DISPATCH):
                time.sleep(pause)
    with span(prof.FEED):               # not a step: left out
        time.sleep(0.05)
    took = [1e-6 * (s.end_ns - s.start_ns) for s in prof.snapshot()[0]
            if s.name == prof.STEP]
    got = read(_run())
    assert got == pytest.approx(statistics.median(took))
    assert 4.0 <= got < 30.0            # the median, not the mean


def test_ps_program_load_s_sums_compiles_under_program_spans():
    import jax
    import jax.numpy as jnp
    import numpy as np
    read = spec.load_reader("ps_program_load_s")
    x = jnp.arange(5.0) + 0.0
    salt = float(np.random.default_rng().integers(1 << 30))
    f = jax.jit(lambda v: v * salt)     # programs no cache has seen
    g = jax.jit(lambda v: v - salt)
    prof.clear()
    with span(prof.TABLE_INIT):
        f(x).block_until_ready()
    with span("bench.own_jit"):         # the benchmark's own: left out
        g(x).block_until_ready()
    compiles = [s for s in prof.snapshot()[0] if s.name == prof.COMPILE]
    assert {s.parent_name for s in compiles} == {prof.TABLE_INIT,
                                                 "bench.own_jit"}
    want = 1e-9 * sum(s.end_ns - s.start_ns for s in compiles
                      if s.parent_name == prof.TABLE_INIT)
    assert read(_run()) == pytest.approx(want)
    assert want > 0


@pytest.mark.parametrize("metric", ["ps_host_ms_per_step",
                                    "ps_program_load_s"])
def test_a_reader_reports_nothing_from_an_empty_ring(metric):
    assert spec.load_reader(metric)(_run()) is None


def test_a_reader_reports_nothing_where_the_program_has_no_ring(
        monkeypatch):
    """The parent commit's profiling module has no ``snapshot``: the
    reader returns None there and does not raise."""
    monkeypatch.delattr(prof, "snapshot")
    for metric in ("ps_host_ms_per_step", "ps_program_load_s"):
        assert spec.load_reader(metric)(_run()) is None


def test_benchmark_json_lists_both_metrics_for_the_lm_cell():
    cell = spec.load_cell("gpt2-xl.t1024-b16")
    per = {m["name"]: m for m in cell.per_layer}
    host, load = per["ps_host_ms_per_step"], per["ps_program_load_s"]
    assert (host["source"], host["moves"], host["unit"]) == (
        "program_span", "samples_per_s_chip", "ms")
    assert (load["source"], load["moves"], load["unit"]) == (
        "program_counter", "setup_s", "s")
    assert "workloads" not in host and "workloads" not in load
    # appended: the entries the benchmark had keep their places
    names = [m["name"] for m in spec.load_benchmark()["per_layer"]]
    assert names[-2:] == ["ps_host_ms_per_step", "ps_program_load_s"]
    assert names[:7] == ["input_ms_per_step", "step_ms_p50",
                         "device_ms_per_step", "device_idle", "peak_hbm",
                         "attn_roofline", "step_mfu.lm"]


def test_a_traced_tiny_run_prints_both_metrics(tmp_path):
    import io
    import json

    import tiny
    root = tiny.make_root(str(tmp_path), cells=["gpt2-xl.t1024-b16"])
    out = io.StringIO()
    rc = harness.run_cell("gpt2-xl.t1024-b16", 2600000123, 1.0, True,
                          require_tpu=False, root=root, out=out,
                          err=io.StringIO())
    assert rc == 0
    metrics = json.loads(out.getvalue().strip().splitlines()[-1])["metrics"]
    assert metrics["ps_host_ms_per_step"]["unit"] == "ms"
    assert metrics["ps_host_ms_per_step"]["value"] > 0
    assert metrics["ps_program_load_s"]["unit"] == "s"
    assert metrics["ps_program_load_s"]["value"] > 0
