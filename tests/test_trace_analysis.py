"""Trace analysis — the read side of the profiling subsystem: from a
captured ``.xplane.pb`` to time by named phase, kernel, step and host
span. The reduction is pinned on a small recorded trace
(``tests/data/fused_step_xspace.txt``, in xplane.proto's text form) and a
real capture on the CPU backend is round-tripped."""

import os

import pytest

from minips_tpu.utils import profiling as prof
from minips_tpu.utils.trace_analysis import (
    HostSpan,
    Op,
    attribute_gaps,
    idle_gaps,
    latest_xplane,
    read_metadata,
    read_xplane,
    seconds_in,
    summarize,
)

HERE = os.path.dirname(os.path.abspath(__file__))
US = 1e-6


@pytest.fixture()
def recorded(tmp_path):
    """The recorded trace written where the profiler would write it."""
    from jax.profiler import ProfileData

    with open(os.path.join(HERE, "data", "fused_step_xspace.txt")) as f:
        raw = ProfileData.text_proto_to_serialized_xspace(f.read())
    d = tmp_path / "plugins" / "profile" / "run1"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(raw)
    return str(tmp_path)


def test_overlapping_ops_count_once_and_idle_is_what_is_left(recorded):
    """Busy time is the union of the op intervals (a while and its body
    overlap); only the ``XLA Ops`` line counts; the idle share is the
    rest of the window."""
    out = summarize(recorded)
    assert out["source"] == "device" and out["devices"] == 1
    assert out["window_s"] == pytest.approx(120 * US)
    assert out["busy_s"] == pytest.approx(100 * US)     # not 130
    assert out["idle_share_pct"] == pytest.approx(100 * 20 / 120, abs=1e-3)
    ops = [Op("a", "", "", 0.0, 4.0), Op("b", "", "", 1.0, 2.0),
           Op("c", "", "", 6.0, 1.0)]
    assert seconds_in(ops, 0.0, 10.0) == pytest.approx(5.0)
    assert seconds_in(ops, 3.0, 6.5) == pytest.approx(1.5)
    assert idle_gaps(ops, 0.0, 10.0) == [[4.0, 6.0], [7.0, 10.0]]


def test_phases_by_scope_forward_apart_from_backward(recorded):
    """Time by the innermost named scope of an op's ``tf_op`` path, union
    inside a phase, forward / backward / rematerialised forward apart;
    an op without a scope is listed by name; kernels by their name."""
    out = summarize(recorded)
    phases = {(r["phase"], r["part"]): r["s"] for r in out["phases"]}
    assert phases == {
        (prof.LM_HEAD, "fwd"): pytest.approx(40 * US),   # while + body
        (prof.LM_HEAD, "bwd"): pytest.approx(20 * US),
        (prof.LM_ATTN, "remat"): pytest.approx(10 * US),
        (prof.UPDATE, "fwd"): pytest.approx(20 * US),
    }
    assert out["named_share_pct"] == pytest.approx(90.0)
    assert [(r["op"], r["category"]) for r in out["unnamed_ops"]] == [
        ("copy.1", "data formatting")]
    (k,) = out["kernels"]
    assert (k["kernel"], k["calls"]) == (prof.FLASH_FWD, 1)
    assert k["s"] == pytest.approx(10 * US)
    assert [(s["step"], round(s["device_s"] / US)) for s in out["steps"]] \
        == [(7, 80), (8, 20)]


def test_a_gap_goes_to_the_innermost_program_span(recorded):
    """Every idle instant has one owner: the innermost ``ps.*`` /
    ``loop.*`` span open then, else the caller's innermost annotation,
    else ``other``; a runtime event is no annotation."""
    gaps = {r["span"]: r["s"] for r in summarize(recorded)["idle_gaps"]}
    assert gaps == {
        "bench.wait": pytest.approx(5 * US),         # 80-85
        prof.FEED: pytest.approx(5 * US),            # 85-90
        prof.STEP: pytest.approx(4 * US),            # 90-92, 98-100
        prof.STEP_DISPATCH: pytest.approx(6 * US),   # 92-98
    }
    spans = [HostSpan("bench.loop", 0.0, 10.0), HostSpan("ps.step", 2.0, 2.0),
             HostSpan("bench.inner", 2.5, 1.0)]
    # the program's span wins over a caller's opened inside it
    assert attribute_gaps([[1.0, 5.0], [11.0, 12.0]], spans) == {
        "bench.loop": pytest.approx(2.0), "ps.step": pytest.approx(2.0),
        "other": pytest.approx(1.0)}


def test_scope_paths_are_read_from_the_event_metadata(recorded):
    """``ProfileData`` hands out an event's own stats; the scope path is
    the ``tf_op`` stat of the event's metadata, read from the file."""
    path = latest_xplane(recorded)
    meta = read_metadata(path)["/device:TPU:0"]
    name = next(n for n in meta if n.startswith("%flash_fwd.2 ="))
    assert meta[name]["hlo_category"] == "custom-call"
    assert meta[name]["tf_op"].endswith("lm.attn/flash_fwd/pallas_call:")
    tr = read_xplane(path)
    assert [o.name for o in tr.devices["0"]][:3] == [
        "while.3", "fusion.7", "fusion.9"]
    assert [s.step for s in tr.spans if s.name == prof.STEP] == [7, 8]
    assert "PjRtClient::Execute" not in {s.name for s in tr.spans}


def test_latest_xplane_picks_newest(tmp_path):
    old = tmp_path / "a" / "x.xplane.pb"
    new = tmp_path / "b" / "y.xplane.pb"
    for p in (old, new):
        p.parent.mkdir()
        p.write_bytes(b"")
    os.utime(old, (1, 1))
    assert latest_xplane(str(tmp_path)) == str(new)


def test_summarize_missing_dir(tmp_path):
    out = summarize(str(tmp_path / "nothing"))
    assert "error" in out


def test_roundtrip_real_capture(tmp_path):
    """profile_trace -> summarize on the CPU backend: a CPU trace has no
    device plane and no scope stat, so its ops come from the host plane,
    unnamed, and the program's spans still mark the steps."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: (x @ x.T).sum())
    x = jnp.ones((256, 256))
    f(x).block_until_ready()
    with prof.profile_trace(str(tmp_path)):
        for _ in range(2):
            with prof.span(prof.STEP):
                y = f(x)
            y.block_until_ready()
    out = summarize(str(tmp_path))
    assert "error" not in out, out
    assert out["source"] == "host"
    assert out["busy_s"] > 0 and out["unnamed_ops"], out
    assert len(out["steps"]) == 2
    assert out["steps"][1]["step"] == out["steps"][0]["step"] + 1
