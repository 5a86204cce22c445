"""The ZAYA cell (``zaya1-8b.t8192-b4``): its files are found by name, a
tiny copy runs whole through the harness and is ``correct``, the control
and every planted fault come out not correct, the cost functions agree
with counts made by hand, and the three readers read what they say."""

import io
import json
import os

import pytest

import tiny_zaya
from benchlib import check, costs, costs_zaya, harness, spec
from benchlib import trace as tracelib

CELL = tiny_zaya.CELL
SEEDS = (2800000000, 2800007919, 2800039595)


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_zaya.make_root(str(tmp_path_factory.mktemp("tiny_zaya")))


# ------------------------------------------------------------ the files
def test_the_cells_files_are_found_by_name():
    cell = spec.load_cell(CELL)
    assert (cell.chips, cell.config_name, cell.traffic_name) == (
        1, "zaya1-8b", "packed-t8192-b4")
    assert cell.config["system"] == "zaya"
    mix = {k: v for k, v in cell.traffic.items() if k != "assumed"}
    assert mix == {"kind": "lm_tokens", "batch": 4, "seq_len": 8192,
                   "vocab": 32784, "zipf_alpha": 1.05, "pool_batches": 16,
                   "warmup_steps": 2, "trace_seconds": 3.0}
    assert {m["name"] for m in cell.end_to_end} == {
        "samples_per_s_chip", "tokens_per_s_chip", "loss_at_n", "setup_s"}
    per = {m["name"]: m for m in cell.per_layer}
    assert set(per) == {
        "input_ms_per_step", "step_ms_p50", "device_ms_per_step",
        "device_idle", "peak_hbm", "ps_host_ms_per_step",
        "ps_program_load_s", "step_mfu.zaya", "moe_roofline",
        "attn_roofline.zaya"}
    for name in ("step_mfu.zaya", "moe_roofline", "attn_roofline.zaya"):
        assert per[name]["workloads"] == [CELL]
        assert per[name]["moves"] == "samples_per_s_chip"
        assert callable(spec.load_reader(name))
    assert set(cell.workload["limits"]) == {
        "loss_step1", "loss_step2", "loss_step3", "grad_worst_leaf",
        "delta_worst_leaf"}
    assert callable(spec.load_system("zaya").build)


def test_the_accepted_entries_keep_their_places_and_only_grow():
    bm = spec.load_benchmark()
    assert [c["name"] for c in bm["configs"]] == ["gpt2-xl", "zaya1-8b"]
    assert [w["name"] for w in bm["workloads"]] == ["gpt2-xl.t1024-b16",
                                                    CELL]
    names = [m["name"] for m in bm["per_layer"]]
    assert names[:9] == [
        "input_ms_per_step", "step_ms_p50", "device_ms_per_step",
        "device_idle", "peak_hbm", "attn_roofline", "step_mfu.lm",
        "ps_host_ms_per_step", "ps_program_load_s"]
    assert names[9:] == ["step_mfu.zaya", "moe_roofline",
                         "attn_roofline.zaya"]
    tokens = [m for m in bm["end_to_end"]
              if m["name"] == "tokens_per_s_chip"][0]
    assert tokens["workloads"] == ["gpt2-xl.t1024-b16", CELL]
    old = {m["name"]: m for m in bm["per_layer"]}
    assert old["attn_roofline"]["workloads"] == ["gpt2-xl.t1024-b16"]
    assert old["step_mfu.lm"]["workloads"] == ["gpt2-xl.t1024-b16"]


@pytest.mark.parametrize("cell", ["gpt2-xl.t1024-b16", CELL])
def test_both_program_metrics_are_due_in_every_training_cell(cell):
    """What test_program_spans.py asks of the dense cell holds of each
    training cell: PR 26's two metrics, with no ``workloads`` list."""
    per = {m["name"]: m for m in spec.load_cell(cell).per_layer}
    host, load = per["ps_host_ms_per_step"], per["ps_program_load_s"]
    assert (host["source"], host["moves"], host["unit"]) == (
        "program_span", "samples_per_s_chip", "ms")
    assert (load["source"], load["moves"], load["unit"]) == (
        "program_counter", "setup_s", "s")
    assert "workloads" not in host and "workloads" not in load


def test_the_configuration_holds_the_published_widths_and_its_cut():
    c = spec.load_cell(CELL).config
    published = {
        "attention_bias": False, "cca_time0": 2, "cca_time1": 2,
        "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
        "lm_head_bias": False, "max_position_embeddings": 131072,
        "model_type": "zaya", "moe_intermediate_size": 2048,
        "num_attention_heads": 8, "num_experts_per_tok": 1,
        "num_key_value_heads": 2, "partial_rotary_factor": 0.5,
        "rms_norm_eps": 1e-05, "router_hidden_size": 256,
        "sliding_window": None, "tie_word_embeddings": True}
    for key, value in published.items():
        assert c[key] == value, key
    assert c["layer_types"] == ["hybrid"] * 40
    assert c["rope_parameters"]["hybrid"] == {
        "partial_rotary_factor": 0.5, "rope_theta": 5000000,
        "rope_type": "default"}
    assert c["reduced"] == ["num_hidden_layers", "num_experts",
                            "vocab_size"]
    assert (c["num_hidden_layers"], c["num_experts"], c["vocab_size"]) == (
        4, 8, 32784)
    assert c["published"] == {"num_hidden_layers": 40, "num_experts": 16,
                              "vocab_size": 262272}
    assert c["vocab_size"] * 8 == c["published"]["vocab_size"]
    assert c["held_experts"] == [0, 8]
    assert c["router_bias_rate"] > 0 and "remat" not in c
    for key in ("deployment", "precision", "departures", "assumed",
                "source"):
        assert c[key], key
    for form in ("value_shift", "convolutions", "qk_mean", "qk_norm",
                 "rotary", "router"):
        assert "[r]" in c["assumed"][form] or "[c]" in c["assumed"][form]


# ------------------------------------------------------- the cost functions
def test_parameter_counts_by_hand():
    c = spec.load_cell(CELL).config
    p = costs_zaya.zaya_params(c)
    assert p["attn"] == 2048 * 1024 + 2048 * 256 + 2 * 2048 * 128 \
        + 1024 * 2048 == 5242880
    assert p["conv"] == 2 * (1024 + 256) + 2 * (8 + 2) * 128 * 128
    assert p["router"] == 2048 * 256 + 2 * 256 * 256 + 256 * 16 + 512
    assert p["experts"] == 8 * 3 * 2048 * 2048
    assert p["embed"] == 32784 * 2048
    # what the program's tree holds (my compile, PR 28)
    assert p["total"] == 4 * p["layer"] + p["embed"] + 2048 == 494745608


def test_flops_by_hand():
    c = spec.load_cell(CELL).config
    routed = 4 * 16384          # half of 32,768 tokens in each of 4 layers
    assert costs_zaya.moe_flops_per_step(c, routed) == \
        6 * 3 * 2048 * 2048 * routed
    per_token_layer = 6 * (5242880 + 330240 + 2048 * 256 + 2 * 256 * 256
                           + 256 * 16) + 6 * 8192 * 8 * 128
    want = 32768 * (4 * per_token_layer + 6 * 32784 * 2048) \
        + 6 * 3 * 2048 * 2048 * routed
    assert costs_zaya.zaya_flops_per_step(c, 4, 8192, routed) == want
    assert 29.0e12 < want < 30.0e12
    # the scores as costs.py counts them for the dense LM's kernels
    assert costs.attention_flops_per_step(4, 8192, 8, 128, 4) == \
        32768 * 4 * 6 * 8192 * 8 * 128


# --------------------------------------------------------------- the readers
def _fake_run(ops, routed=4 * 16384, peak=197e12, steps=2):
    cell = spec.load_cell(CELL)
    tr = tracelib.Trace(devices={0: [tracelib.Op(*o) for o in ops]})
    return harness.Run(
        cell=cell, chips=1, config=cell.config, traffic=cell.traffic,
        peaks={"bf16_flops_per_s": peak} if peak else None,
        info={"routed_tokens_held": routed} if routed is not None else {},
        trace=tr, traced_steps=steps, n_steps=50, window_s=25.0,
        trace_summary={"lo": 0.0, "hi": 10.0})


OPS = [
    # name, opcode, shapes, start, seconds
    ("ragged-dot-none.3", "custom-call",
     "bf16[32768,2048] <- s32[1],s32[9],bf16[32768,2048],bf16[8,2048,2048]",
     0.0, 0.010),
    ("ragged-dot-none.9", "custom-call",
     "f32[8,2048,2048] <- s32[9],bf16[32768,2048],bf16[32768,2048]",
     0.1, 0.020),
    ("convert.2", "fusion", "bf16[8,2048,2048] <- f32[494745608]", 0.2, 1.0),
    ("fusion.7", "fusion", "bf16[32768,2048] <- bf16[32768,2048]", 1.3, 1.0),
    ("flash_fwd.4", "custom-call", "bf16[4,8,8192,128] <- s32[1]", 2.5, 0.05),
    ("flash_dkv.2", "custom-call", "bf16[4,2,8192,128] <- s32[1]", 2.6, 0.05),
]


def test_moe_roofline_reads_the_ops_with_stack_and_token_rows():
    run = _fake_run(OPS)
    c = run.config
    want = 100.0 * costs_zaya.moe_flops_per_step(c, 4 * 16384) / 197e12 \
        / (0.030 / 2)
    assert spec.load_reader("moe_roofline")(run) == pytest.approx(want)
    assert spec.load_reader("moe_roofline")(_fake_run(OPS[2:])) is None
    assert spec.load_reader("moe_roofline")(_fake_run(OPS, None)) is None


def test_attn_roofline_zaya_reads_the_kernels_named_flash():
    run = _fake_run(OPS)
    want = 100.0 * costs.attention_flops_per_step(4, 8192, 8, 128, 4) \
        / 197e12 / (0.10 / 2)
    assert spec.load_reader("attn_roofline.zaya")(run) == pytest.approx(want)
    assert spec.load_reader("attn_roofline.zaya")(_fake_run(OPS[:4])) is None


def test_step_mfu_zaya_counts_the_tokens_really_routed():
    read = spec.load_reader("step_mfu.zaya")
    c = spec.load_cell(CELL).config
    even = read(_fake_run(OPS))
    assert even == pytest.approx(
        100.0 * costs_zaya.zaya_flops_per_step(c, 4, 8192, 65536) * 2.0
        / 197e12)
    assert read(_fake_run(OPS, routed=0)) < even
    assert read(_fake_run(OPS, None)) is None       # no routing observer
    assert read(_fake_run(OPS, peak=None)) is None  # no chip


# ------------------------------------------------------ a tiny copy, whole
def _run(root, seed, trace=False, wrap=None):
    out, err = io.StringIO(), io.StringIO()
    rc = harness.run_cell(CELL, seed, 0.5, trace, require_tpu=False,
                          root=root, out=out, err=err, wrap_system=wrap)
    assert rc == 0, err.getvalue()
    return json.loads(out.getvalue().strip().splitlines()[-1]), \
        err.getvalue()


def test_a_tiny_copy_of_the_cell_runs_and_is_correct(root, capsys):
    line, _ = _run(root, SEEDS[0])
    err = capsys.readouterr().err
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"setup_s", "samples_per_s_chip",
                                    "tokens_per_s_chip", "loss_at_n"}
    for row in line["check"].values():
        assert row["value"] <= row["limit"]
    assert line["compiles"]["window"]["cache_misses"] == 0
    assert "tokens of each held expert" in err      # the routing observer
    assert "whose expert differs from the reference's" in err


def test_a_traced_tiny_run_reports_the_layers_a_cpu_can(root):
    """No chip, so no peaks: the three shares report nothing and do not
    raise; the seven metrics every training cell owes are there but those
    a CPU trace has no device plane for."""
    line, _ = _run(root, SEEDS[1], trace=True)
    assert {"input_ms_per_step", "step_ms_p50", "ps_host_ms_per_step",
            "ps_program_load_s"} <= set(line["metrics"])
    assert not {"step_mfu.zaya", "moe_roofline",
                "attn_roofline.zaya"} & set(line["metrics"])


def test_an_unchanged_state_is_not_correct(root):
    def wrap(system):
        import jax.numpy as jnp
        system.step = lambda batch: jnp.float32(0.5)
        return system
    line, _ = _run(root, SEEDS[2], wrap=wrap)
    assert line["correct"] is False
    assert line["check"]["delta_worst_leaf"]["value"] == pytest.approx(
        1.0, abs=1e-3)


def _readings(root, seed):
    cell = spec.load_cell(CELL, root)
    mod = spec.load_system("zaya")
    phases = harness.Phases(harness.process_start_time())
    system = mod.build(cell, seed, phases)
    prog = harness.first_readings(system)
    system.free()
    ref = system.reference()
    assert check.decide(prog, ref, cell.workload["limits"])[0]
    return mod, system, ref, cell.workload["limits"], phases


@pytest.mark.parametrize("seed", SEEDS)
def test_the_control_and_every_planted_fault_fail(root, seed):
    """The control (scaled fp8), half a batch and an expert layer that
    drops what overflows a capacity of 1.0: each not correct, on every
    seed. The balancing bias only centres the routers at the start, so
    the first steps' loads are uneven and the capacity does drop."""
    mod, system, ref, limits, phases = _readings(root, seed)
    assert len(system.flips) == 2 and max(system.flips) <= 16
    assert set(mod.FAULTS) == {"fault_half_batch", "fault_capacity_drops"}
    for name, kw in mod.FAULTS.items():
        ok, rows = check.decide(system.reference(**kw), ref, limits)
        assert not ok, (name, rows)
    control = mod.control_readings(system, phases)
    assert all(v == v and abs(v) < 1e30 for v in control["loss"])  # finite
    ok, rows = check.decide(control, ref, limits)
    assert not ok, rows


@pytest.mark.parametrize("seed", SEEDS)
def test_the_reference_starts_from_its_own_bias_and_hands_it_on(root, seed):
    """The sound reference centres its own balancing bias; the faults and
    the control start from that one, not from the program's."""
    _, system, ref, _, _ = _readings(root, seed)
    import numpy as np
    assert ref["bias"].shape == (2, 4)
    np.testing.assert_allclose(ref["bias"], np.asarray(system._bias0),
                               atol=2e-3)
    again = system.reference(keep=0.5)
    np.testing.assert_array_equal(again["bias"], ref["bias"])


def test_the_capacity_fault_drops_what_overflows():
    """At capacity 1.0 an expert keeps its first tokens/experts tokens:
    the reference's gate is zero past them."""
    import jax
    import jax.numpy as jnp
    from benchlib.reference import zaya_ref
    from minips_tpu.models import zaya
    with open(os.path.join(tiny_zaya.ROOT, "bench", "configs",
                           "zaya1-8b.json")) as f:
        c = dict(json.load(f), **tiny_zaya.CONFIG)
    c["published"] = dict(c["published"], num_experts=4)
    blk = zaya.init(jax.random.PRNGKey(0), zaya.from_config(c))["blocks"][0]
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, 32))
    r0 = jnp.zeros((64, 8))
    z = zaya_ref._sizes(c)
    b0 = jnp.zeros(4)
    whole, _, e, _ = zaya_ref.experts(blk, x, r0, b0, z, False)
    cut, _, _, _ = zaya_ref.experts(blk, x, r0, b0, z, False, capacity=1.0)
    e = jax.device_get(e)
    kept = jnp.any(cut.reshape(64, 32) != 0, axis=1)
    for i in (0, 1):            # the experts held
        mine = [t for t in range(64) if e[t] == i]
        for t in mine[:16]:
            assert bool(kept[t])
        for t in mine[16:]:
            assert not bool(kept[t])
    assert bool(jnp.any(whole != cut)) == any(
        sum(1 for t in range(64) if e[t] == i) > 16 for i in (0, 1))
