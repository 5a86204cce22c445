"""bench.py harness contract: one JSON line, FLOP-accounted fields, and
the off-TPU vs_baseline refusal (VERDICT r1 weak #7 / next-round #2)."""

import json
import os
import pathlib
import subprocess
import sys

import pytest

REPO = str(pathlib.Path(__file__).resolve().parents[1])


@pytest.mark.slow
def test_bench_cpu_emits_accounted_json():
    proc = subprocess.run(
        [sys.executable, "bench.py", "--cpu", "--suite", "lrmlp",
         "--batch", "512", "--chain", "2", "--reps", "2"],
        capture_output=True, text=True, timeout=420, cwd=REPO)
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = [ln for ln in proc.stdout.splitlines()
            if ln.startswith("{")][-1]
    out = json.loads(line)
    assert out["unit"] == "samples/sec/chip"
    assert out["value"] > 0
    # a CPU run must never publish a TPU-comparable ratio
    assert out["vs_baseline"] is None
    s = out["suites"]["lrmlp"]
    assert s["tflops_per_chip"] > 0
    assert "mfu_vs_bf16_peak" in s and s["mfu_vs_bf16_peak"] is None
    assert "warning" not in s


@pytest.mark.slow
@pytest.mark.parametrize("suite", ["mf", "w2v"])
def test_bench_embedding_suites_cpu(suite):
    """Round-3 suites for BASELINE configs 3 (MF/MovieLens) and 5
    (word2vec/enwiki): same harness contract — one JSON line, accounted
    fields, off-TPU vs_baseline refusal."""
    proc = subprocess.run(
        [sys.executable, "bench.py", "--cpu", "--suite", suite,
         "--batch", "512", "--chain", "2", "--reps", "2"],
        capture_output=True, text=True, timeout=420, cwd=REPO)
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = [ln for ln in proc.stdout.splitlines()
            if ln.startswith("{")][-1]
    out = json.loads(line)
    assert out["unit"] == "samples/sec/chip"
    assert out["value"] > 0
    assert out["vs_baseline"] is None          # off-TPU refusal holds
    assert suite in out["metric"]              # never labeled as LR+MLP
    s = out["suites"][suite]
    assert s["tflops_per_chip"] > 0
    assert s["mfu_vs_bf16_peak"] is None
    assert "warning" not in s


def test_sharded_ps_bench_worker_standalone():
    """Zero-wire baseline mode (no launcher): the worker runs, counts, and
    reports the protocol fields — the n=1 point of bench_sharded_ps.py."""
    proc = subprocess.run(
        [sys.executable, "-m", "minips_tpu.apps.sharded_ps_bench",
         "--path", "sparse", "--iters", "8", "--warmup", "2",
         "--rows", "4096", "--batch", "512"],
        capture_output=True, text=True, timeout=120, cwd=REPO)
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = json.loads([ln for ln in proc.stdout.splitlines()
                      if ln.startswith("{")][-1])
    assert out["event"] == "done" and out["nprocs"] == 1
    assert out["bus"] == "none"
    assert out["rows_per_sec"] > 0
    assert out["wire_push_bytes_per_sec"] == 0  # nothing rides a wire


def test_sharded_ps_bench_worker_jit_compute():
    """--compute jit (the ps_tpu suite's worker): a real jitted MLP grad
    runs on the pulled rows between pull and push. Forced-CPU here; the
    result must label the backend it ran on and still count rows/wire."""
    proc = subprocess.run(
        [sys.executable, "-m", "minips_tpu.apps.sharded_ps_bench",
         "--path", "sparse", "--iters", "8", "--warmup", "2",
         "--rows", "4096", "--batch", "512", "--compute", "jit",
         "--hidden", "64"],
        capture_output=True, text=True, timeout=180,
        cwd=REPO, env={**os.environ, "MINIPS_FORCE_CPU": "1"})
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = json.loads([ln for ln in proc.stdout.splitlines()
                      if ln.startswith("{")][-1])
    assert out["event"] == "done" and out["compute"] == "jit(cpu)"
    assert out["rows_per_sec"] > 0


@pytest.mark.slow
def test_sharded_ps_bench_floor_two_processes():
    """Regression floor for the sharded-PS data path (VERDICT r2 #2): a
    2-process loopback sparse pull+push must sustain >100k rows/sec per
    process (measured ~1.5M on this class of host — 15x headroom so CI
    noise can't flake it) and drop zero frames (asserted in-worker)."""
    from minips_tpu import launch

    res = launch.run_local_job(
        2, [sys.executable, "-m", "minips_tpu.apps.sharded_ps_bench",
            "--path", "sparse", "--iters", "24", "--warmup", "4"],
        base_port=6590, timeout=240.0)
    assert len(res) == 2
    for r in res:
        assert r["event"] == "done" and r["nprocs"] == 2
        assert r["rows_per_sec"] > 100_000, r
        assert r["wire_push_bytes_per_sec"] > 0  # wire actually engaged


@pytest.mark.parametrize("suite", ["lrmlp", "all"])
def test_bench_without_a_tpu_exits_at_once(suite):
    """No probe, no fallback: a chip suite that finds no TPU exits 3 with
    a message and prints no result — and ``--suite all`` stops at the
    first child that says so."""
    proc = subprocess.run(
        [sys.executable, "bench.py", "--suite", suite],
        capture_output=True, text=True, timeout=120, cwd=REPO,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 3, proc.stderr[-2000:]
    assert "no TPU" in proc.stderr
    assert proc.stdout.strip() == ""


def test_unknown_device_kind_has_no_peak():
    import types

    sys.path.insert(0, REPO)
    import bench

    assert bench._peak_for(types.SimpleNamespace(
        device_kind="TPU v5 lite")) == 197e12
    with pytest.raises(SystemExit, match="TPU v5 lite chip"):
        bench._peak_for(types.SimpleNamespace(
            device_kind="TPU v5 lite chip"))  # no prefix match


def test_ssp_schedule_simulation_invariants():
    """The event-driven gate schedule (bench_ssp.simulate_schedule) obeys
    the theory: BSP pays the union of stalls, staleness only helps, zero
    jitter makes all modes equal, and large s approaches the no-barrier
    bound (slowest worker's own work)."""
    sys.path.insert(0, REPO)
    from bench_ssp import simulate_schedule

    kw = dict(n=3, iters=200, step_ms=20.0, jitter_ms=40.0,
              jitter_prob=0.25, seed=1)
    bsp = simulate_schedule(staleness=0, **kw)
    ssp = simulate_schedule(staleness=4, **kw)
    free = simulate_schedule(staleness=10**6, **kw)
    assert free <= ssp <= bsp
    assert bsp > ssp * 1.05            # jitter regime: SSP genuinely wins
    # no jitter: the barrier costs nothing, every mode identical
    kw0 = dict(kw, jitter_ms=0.0)
    assert simulate_schedule(staleness=0, **kw0) == \
        simulate_schedule(staleness=4, **kw0)
    # the no-barrier bound equals the slowest worker's own serial time
    import numpy as np
    rng = np.random.default_rng(1)
    stall = (rng.random((3, 200)) < 0.25) * 40.0
    serial = (200 * 20.0 + stall.sum(axis=1)).max() / 1000.0
    assert abs(free - serial) < 1e-9
