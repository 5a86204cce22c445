"""What keeps the device from hiding: the chip smoke fails without a TPU,
the compile cache is placed from outside, local multi-rank jobs state
their devices, and no native binary of unknown origin is loaded."""

from __future__ import annotations

import json
import os
import pathlib
import subprocess
import sys

import jax
import pytest

from minips_tpu import launch
from minips_tpu.utils import compile_cache, native_lib

REPO = str(pathlib.Path(__file__).resolve().parents[1])


def _smoke(*args):
    return subprocess.run(
        [sys.executable, "chip_smoke.py", *args], capture_output=True,
        text=True, timeout=300, cwd=REPO,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})


def test_chip_smoke_fails_without_a_tpu():
    proc = _smoke()
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr and "'cpu'" in proc.stderr
    assert proc.stdout.strip() == ""  # no result line to mistake for one


def test_chip_smoke_rehearsal_is_labelled_cpu():
    proc = _smoke("--rehearse-cpu")
    assert proc.returncode == 0, proc.stderr[-2000:]
    out, result = map(json.loads, proc.stdout.splitlines()[-2:])
    # the last line is the result, in exactly the shape the chip check reads
    assert result == {"ok": True, "device": {
        "platform": "cpu", "kind": "cpu", "count": 4}}
    # the line before it holds the facts of the run
    assert out["rehearsal"] == "cpu" and out["device"] == result["device"]
    assert out["native_libs_loaded"] == []
    for leg in ("deepfm", "lm"):
        facts = out["legs"][leg]
        assert facts["loss_last"] < facts["loss_first"]
        assert len(facts["table_bytes_per_device"]) == 4
    # pull ≡ all-gather, push ≡ reduce-scatter in the dp LM step
    assert {"all-gather", "reduce-scatter"} <= set(
        out["legs"]["lm"]["collectives_asked"])
    # the ZAYA block against the benchmark's reference, a share of experts
    zaya = out["legs"]["zaya"]
    assert zaya["shape"]["experts_held"] == [0, 2]
    assert zaya["shape"]["experts"] == 4
    assert abs(zaya["loss"] - zaya["reference_loss"]) < 2e-3 * zaya["loss"]
    assert zaya["grad_norm_worst_gap"] < 0.05 + 4 * zaya["tokens_flipped"] \
        / (zaya["shape"]["B"] * zaya["shape"]["T"])


def test_chip_smoke_alone_fails(tmp_path):
    """A copy with nothing else of the repo beside it fails, and says why,
    before it imports jax (on the chip it would otherwise hold a device)."""
    (tmp_path / "chip_smoke.py").write_bytes(
        pathlib.Path(REPO, "chip_smoke.py").read_bytes())
    for args in ([], ["--rehearse-cpu"]):
        proc = subprocess.run(
            [sys.executable, "chip_smoke.py", *args], capture_output=True,
            text=True, timeout=60, cwd=tmp_path,
            env={**os.environ, "PYTHONPATH": REPO})  # an installed copy
        assert proc.returncode != 0
        assert "no minips_tpu package beside" in proc.stderr
        assert proc.stdout.strip() == ""


@pytest.fixture
def config_updates(monkeypatch):
    """What enable_compile_cache asks of jax.config, recorded not applied
    (the session's own cache stays where conftest put it)."""
    calls = {}
    monkeypatch.setattr(jax.config, "update", calls.__setitem__)
    return calls


def test_compile_cache_honours_the_environment(monkeypatch, config_updates):
    monkeypatch.delenv("MINIPS_NUM_PROCS", raising=False)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    assert compile_cache.enable_compile_cache() == "/somewhere/else"
    # JAX's own reading of the variable stands: no code sets a directory
    assert "jax_compilation_cache_dir" not in config_updates

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    fixed = os.path.join(REPO, ".jax_cache")
    assert compile_cache.enable_compile_cache() == fixed
    assert config_updates["jax_compilation_cache_dir"] == fixed


def test_launcher_ranks_run_cache_less(monkeypatch, config_updates):
    monkeypatch.setenv("MINIPS_NUM_PROCS", "3")
    assert compile_cache.enable_compile_cache() is None
    assert not config_updates
    # ... including when the launcher's own environment names a directory
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    hosts = ["localhost"] * 3
    assert "JAX_COMPILATION_CACHE_DIR" not in launch.child_env(0, hosts, 6000)
    assert launch.child_env(0, hosts[:1], 6000)[
        "JAX_COMPILATION_CACHE_DIR"] == "/somewhere/else"


def test_launcher_refuses_ranks_that_would_share_a_chip(monkeypatch):
    hosts = ["localhost", "127.0.0.1", "localhost"]
    unpinned, cpu = {"JAX_PLATFORMS": "tpu,cpu"}, {"JAX_PLATFORMS": "cpu"}
    with pytest.raises(launch.DeviceClaimError, match=r"ranks \[0, 1, 2\]"):
        launch.check_device_claims(hosts, [unpinned, {}, unpinned])
    # one rank on the default backend, its peers stated on the CPU: fine
    launch.check_device_claims(hosts, [unpinned, cpu,
                                       {"MINIPS_FORCE_CPU": "1"}])
    # one rank per host never shares
    launch.check_device_claims(["a", "b"], [{}, {}])

    # the CLI refuses with the message before it spawns anything
    monkeypatch.delenv("JAX_PLATFORMS")
    monkeypatch.delenv("MINIPS_FORCE_CPU", raising=False)
    monkeypatch.setattr(subprocess, "Popen", lambda *a, **k: pytest.fail(
        "a rank was spawned"))
    with pytest.raises(SystemExit) as e:
        launch.main(["--n", "3", "--", sys.executable, "-c", "pass"])
    assert e.value.code == 2


def test_native_lib_never_loads_a_stale_binary(monkeypatch, capsys):
    """make cannot run → None and a line on stderr, even though a binary
    by that name sits in cpp/build/."""
    def no_make(*a, **k):
        raise FileNotFoundError("make")

    monkeypatch.setattr(subprocess, "run", no_make)
    monkeypatch.setattr(native_lib, "_cache", {})
    monkeypatch.setattr(native_lib.ctypes, "CDLL", lambda p: pytest.fail(
        f"loaded {p} without a build"))
    assert native_lib.load_native_lib("libminips_data.so",
                                      lambda lib: None) is None
    assert "not built from cpp/" in capsys.readouterr().err
    assert native_lib.loaded_libs() == []
