"""Trace analysis — the read side of the profiling subsystem: from a
captured ``.xplane.pb`` to time by named phase, kernel, step and host
span. The reduction is pinned on a small recorded trace
(``tests/data/fused_step_xspace.txt``, in xplane.proto's text form) and a
real capture on the CPU backend is round-tripped."""

import os

import pytest

from minips_tpu.utils import profiling as prof
from minips_tpu.utils import trace_analysis
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


# ------------------------------------ a compiled program's phases, from text
# A compiled step's text in small: instructions as scheduled, callees first.
# The compiler's own instructions carry no ``op_name``.
_STEP = "jit(ps_dense_step)/shard_map"
_HLO = f'''HloModule jit_ps_dense_step, is_scheduled=true

%fused_slice (param_0.1: bf16[4096], param_1.1: f32[64]) -> f32[64] {{
  %param_0.1 = bf16[4096]{{0}} parameter(0)
  %param_1.1 = f32[64]{{0}} parameter(1)
  %split.1 = bf16[64]{{0}} slice(%param_0.1), slice={{[0:64]}}, metadata={{op_name="{_STEP}/ps.pull/split" stack_frame_id=3}}
  %convert.2 = f32[64]{{0}} convert(%split.1), metadata={{op_name="{_STEP}/ps.grad/jvp(lm.mlp)/convert_element_type"}}
  ROOT %mul.3 = f32[64]{{0}} multiply(%convert.2, %param_1.1), metadata={{op_name="{_STEP}/ps.grad/jvp(lm.mlp)/mul"}}
}}

%fused_dot (param_0.3: bf16[4096], param_1.3: f32[64]) -> f32[64] {{
  %param_0.3 = bf16[4096]{{0}} parameter(0)
  %param_1.3 = f32[64]{{0}} parameter(1)
  ROOT %dot.8 = f32[64]{{0}} dot(%param_0.3, %param_1.3), metadata={{op_name="{_STEP}/ps.grad/transpose(jvp(lm.mlp))/dot_general"}}
}}

%fused_adam (param_0.2: f32[4096], param_1.2: f32[1024]) -> f32[1024] {{
  %param_0.2 = f32[4096]{{0}} parameter(0)
  %param_1.2 = f32[1024]{{0}} parameter(1)
  %dynamic-slice.4 = f32[1024]{{0}} dynamic-slice(%param_0.2), dynamic_slice_sizes={{1024}}
  %div.5 = f32[1024]{{0}} multiply(%dynamic-slice.4, %dynamic-slice.4), metadata={{op_name="{_STEP}/ps.push/div"}}
  %mul.6 = f32[1024]{{0}} multiply(%dynamic-slice.4, %param_1.2), metadata={{op_name="{_STEP}/ps.update/mul"}}
  ROOT %add.7 = f32[1024]{{0}} add(%div.5, %mul.6), metadata={{op_name="{_STEP}/ps.update/add"}}
}}

ENTRY %main.9 (p_shard.1: f32[1024], batch.1: f32[64]) -> f32[1024] {{
  %p_shard.1 = f32[1024]{{0}} parameter(0), metadata={{op_name="p_shard"}}
  %batch.1 = f32[64]{{0}} parameter(1), metadata={{op_name="batch"}}
  %convert.260 = bf16[1024]{{0:T(1024)(128)(2,1)}} convert(%p_shard.1)
  %all-gather.4 = bf16[4096]{{0}} all-gather(%convert.260), channel_id=1, replica_groups={{{{0,1,2,3}}}}, dimensions={{0}}, metadata={{op_name="{_STEP}/ps.pull/all_gather" stack_frame_id=3}}
  %reshape.8 = bf16[4096]{{0}} reshape(%all-gather.4)
  %copy-start.1 = (bf16[4096], bf16[4096], u32[]) copy-start(%reshape.8)
  %copy-done.1 = bf16[4096]{{0:S(1)}} copy-done(%copy-start.1)
  %fusion.10 = f32[64]{{0}} fusion(%reshape.8, %batch.1), kind=kLoop, calls=%fused_slice, metadata={{op_name="{_STEP}/ps.grad/jvp(lm.mlp)/mul"}}
  %fusion.11 = f32[64]{{0}} fusion(%copy-done.1, %fusion.10), kind=kOutput, calls=%fused_dot, metadata={{op_name="{_STEP}/ps.grad/transpose(jvp(lm.mlp))/mul"}}
  %copy.12 = f32[64]{{0}} copy(%fusion.11)
  %fusion.13 = f32[64]{{0}} fusion(%copy.12), kind=kLoop, calls=%fused_other, metadata={{op_name="{_STEP}/ps.grad/transpose(jvp(lm.mlp))/checkpoint/rematted_computation/lm.mlp/add"}}
  %concatenate.27 = bf16[4096]{{0}} concatenate(%fusion.13), dimensions={{0}}, metadata={{op_name="{_STEP}/ps.push/concatenate"}}
  %all-reduce.99 = f32[4096]{{0}} all-reduce(%concatenate.27), channel_id=2, replica_groups={{{{0,1,2,3}}}}, to_apply=%region_1
  %bitcast.14 = f32[4096]{{0}} bitcast(%all-reduce.99)
  %sparse.15 = f32[8]{{0}} fusion(%batch.1), kind=kLoop, calls=%fused_other, metadata={{op_name="jit(ps_fused_step)/ps.push.sparse/emb/sparse.dedup/sort"}}
  %loose.16 = f32[8]{{0}} negate(%batch.1), metadata={{op_name="{_STEP}/reshape.976"}}
  %alone.17 = f32[] constant(0)
  ROOT %multiply_add_fusion = f32[1024]{{0}} fusion(%bitcast.14, %p_shard.1), kind=kLoop, calls=%fused_adam, metadata={{op_name="{_STEP}/ps.update/add" stack_frame_id=9}}
}}
'''


@pytest.fixture(scope="module")
def placed():
    from minips_tpu.utils.trace_analysis import instruction_phases

    return instruction_phases(_HLO)


@pytest.mark.parametrize("name,want", [
    # by its own op_name: the outermost PS phase, the innermost named one
    ("all-gather.4", (prof.PULL, prof.PULL, "fwd", "scope")),
    ("fusion.10", (prof.GRAD, prof.LM_MLP, "fwd", "scope")),
    ("fusion.11", (prof.GRAD, prof.LM_MLP, "bwd", "scope")),
    ("fusion.13", (prof.GRAD, prof.LM_MLP, "remat", "scope")),
    ("multiply_add_fusion", (prof.UPDATE, prof.UPDATE, "fwd", "scope")),
    ("sparse.15", (prof.PUSH, prof.SPARSE_DEDUP, "fwd", "scope")),
    ("split.1", (prof.PULL, prof.PULL, "fwd", "scope")),    # in a fusion
    # between two of one phase it takes it
    ("copy.12", (prof.GRAD, prof.GRAD, "fwd", "neighbours")),
    # it reads the step's arguments alone: with its first user
    ("convert.260", (prof.PULL, prof.PULL, "fwd", "neighbours")),
    # a fusion is looked INTO: what reads the vector there is the slice,
    # which kept ``ps.pull/split``, not the fusion's own ``ps.grad``
    ("reshape.8", (prof.PULL, prof.PULL, "fwd", "neighbours")),
    # fed by the push; its scatter, fused into Adam, feeds ``ps.push/div``
    # first and ``ps.update/mul`` after it
    ("all-reduce.99", (prof.PUSH, prof.PUSH, "fwd", "neighbours")),
    ("bitcast.14", (prof.PUSH, prof.PUSH, "fwd", "neighbours")),
    ("dynamic-slice.4", (prof.PUSH, prof.PUSH, "fwd", "neighbours")),
    # between two phases it stays without: a copy of the pulled vector
    # into the memory the gradient's matmul reads it from
    ("copy-start.1", (None, None, "fwd", None)),
    ("copy-done.1", (None, None, "fwd", None)),
    # nothing around it has a phase
    ("alone.17", (None, None, "fwd", None)),
])
def test_an_instruction_is_placed_by_scope_or_by_its_neighbours(
        placed, name, want):
    assert tuple(placed[name]) == want


def test_an_op_name_without_a_phase_is_placed_by_its_neighbours(placed):
    """The partitioner names its own instructions ``.../reshape.976``:
    that is no scope; this one reads an argument and nothing reads it."""
    assert placed["loose.16"].how is None
    assert set(placed) >= {"param_0.1", "mul.6", "batch.1"}   # every one


def test_ps_phase_of_takes_the_outermost_of_the_four():
    from minips_tpu.utils.trace_analysis import phase_of, ps_phase_of

    path = ("jit(ps_dense_step)/ps.grad/transpose(jvp(ps.grad))/jvp()/"
            "checkpoint/rematted_computation/lm.attn/flash_fwd/pallas_call")
    assert ps_phase_of(path) == prof.GRAD
    assert phase_of(path) == (prof.LM_ATTN, "remat")
    assert ps_phase_of("jit(ps_fused_step)/ps.push.dense/add") == prof.PUSH
    assert ps_phase_of(
        "jit(ps_fused_step)/ps.push.sparse/emb/sparse.adagrad_sorted/mul"
    ) == prof.PUSH
    assert ps_phase_of("jit(ps_dense_step)/shard_map/psum_invariant") is None
    assert ps_phase_of("jit(f)/lm.mlp/dot_general") is None
    assert ps_phase_of("") is None


def test_collective_ops_carry_their_instructions_names():
    from minips_tpu.utils.comm_analysis import collective_ops

    ops = collective_ops(_HLO)
    assert [(o.name, o.kind, o.shape, o.bytes) for o in ops] == [
        ("all-gather.4", "all-gather", "bf16[4096]", 8192),
        ("all-reduce.99", "all-reduce", "f32[4096]", 16384)]


def test_a_cpu_trace_is_named_through_the_programs_account(tmp_path, mesh4):
    """A CPU trace's ops carry ``hlo_op`` names and no scope: with
    ``programs.json`` beside the trace they are reported by phase, and
    the step's memory and the collectives built under each PS phase are
    printed; without it they are unnamed, as before."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from minips_tpu.parallel.mesh import DATA_AXIS
    from minips_tpu.tables.dense import DenseTable

    def grad_fn(p, b):
        return jax.value_and_grad(
            lambda p: jnp.mean((b["x"] @ p["w"]) ** 2))(p)

    table = DenseTable({"w": jnp.full((48, 3), 0.48)}, mesh4,
                       updater="adam", lr=0.1)
    step = table.make_step(grad_fn)
    batch = {"x": jax.device_put(jnp.ones((8, 48)),
                                 NamedSharding(mesh4, P(DATA_AXIS)))}
    prof.clear()
    table.step_inplace(step, batch).block_until_ready()
    with prof.profile_trace(str(tmp_path)):
        for _ in range(2):
            table.step_inplace(step, batch).block_until_ready()
    bare = summarize(str(tmp_path))
    assert bare["source"] == "host" and bare["programs"] == {}
    assert not bare["phases"] and bare["unnamed_ops"]
    trace_analysis.dump_programs(str(tmp_path / "programs.json"))
    out = summarize(str(tmp_path))
    assert {r["phase"] for r in out["phases"]} >= {
        prof.PULL, prof.PUSH, prof.UPDATE}
    assert out["named_share_pct"] > bare["named_share_pct"] == 0.0
    acc = out["programs"][prof.DENSE_STEP_FN]
    assert acc["memory"]["total_bytes"] > 0
    assert acc["collectives"][prof.PULL][0].startswith("all-gather f32[")
    assert acc["collectives"][prof.PUSH][0].split()[0] in (
        "reduce-scatter", "all-reduce")
