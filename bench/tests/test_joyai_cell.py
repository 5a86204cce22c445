"""The JoyAI cell (``joyai-llm-flash.t8192-b2``) and the small-batch dense
cell that came with it (``gpt2-xl.t1024-b4``): their files are found by
name, a tiny copy runs whole through the harness and is ``correct``, the
control and every planted fault come out not correct on each seed, the
cost functions agree with counts made by hand, the three readers read
what they say and give nothing where there is nothing to read, and the
accepted entries keep their places (asserted as prefixes, so that the next
addition does not break this file)."""

import io
import json

import pytest

import tiny_joyai
from benchlib import check, costs_joyai, harness, spec
from benchlib import trace as tracelib

CELL = tiny_joyai.CELL
SMALL = "gpt2-xl.t1024-b4"
SEEDS = (3300000000, 3300015838, 3300023757)
READERS = ("step_mfu.joyai", "attn_roofline.joyai", "moe_roofline.joyai")


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_joyai.make_root(str(tmp_path_factory.mktemp("tiny_joyai")))


# ------------------------------------------------------------ the files
def test_the_cells_files_are_found_by_name():
    cell = spec.load_cell(CELL)
    assert (cell.chips, cell.config_name, cell.traffic_name) == (
        1, "joyai-llm-flash", "packed-t8192-b2")
    assert cell.config["system"] == "joyai"
    mix = {k: v for k, v in cell.traffic.items() if k != "assumed"}
    assert mix == {"kind": "lm_tokens", "batch": 2, "seq_len": 8192,
                   "vocab": 16160, "zipf_alpha": 1.05, "pool_batches": 16,
                   "warmup_steps": 2, "trace_seconds": 4.0}
    assert {m["name"] for m in cell.end_to_end} >= {
        "samples_per_s_chip", "tokens_per_s_chip", "loss_at_n", "setup_s"}
    per = {m["name"]: m for m in cell.per_layer}
    # at least these: a metric appended without a ``workloads`` list is
    # due here too
    assert set(per) >= {
        "input_ms_per_step", "step_ms_p50", "device_ms_per_step",
        "device_idle", "peak_hbm", "ps_host_ms_per_step",
        "ps_program_load_s", *READERS}
    for name in READERS:
        assert per[name]["workloads"][:1] == [CELL]
        assert per[name]["moves"] == "tokens_per_s_chip"
        assert callable(spec.load_reader(name))
    assert set(cell.workload["limits"]) == {
        "loss_step1", "loss_step2", "loss_step3", "grad_worst_leaf",
        "delta_worst_leaf"}
    assert cell.workload["loss_steps"] == [9, 24]
    assert callable(spec.load_system("joyai").build)


def test_the_small_batch_cell_is_the_dense_configuration_with_batch_4():
    small, big = spec.load_cell(SMALL), spec.load_cell("gpt2-xl.t1024-b16")
    assert small.config == big.config and small.chips == 1
    strip = lambda m: {k: v for k, v in m.items()       # noqa: E731
                       if k not in ("assumed", "batch")}
    assert strip(small.traffic) == strip(big.traffic)
    assert (small.traffic["batch"], big.traffic["batch"]) == (4, 16)
    assert small.workload["loss_steps"] == [9, 24]
    # loss_step1 is left out: the control reads like a sound run there
    assert set(small.workload["limits"]) == set(
        big.workload["limits"]) - {"loss_step1"}
    assert {m["name"] for m in small.per_layer} == {
        m["name"] for m in big.per_layer}


def test_the_accepted_entries_keep_their_places_as_prefixes():
    bm = spec.load_benchmark()
    assert [c["name"] for c in bm["configs"]][:3] == [
        "gpt2-xl", "zaya1-8b", "joyai-llm-flash"]
    assert [w["name"] for w in bm["workloads"]][:4] == [
        "gpt2-xl.t1024-b16", "zaya1-8b.t8192-b4", CELL, SMALL]
    assert [m["name"] for m in bm["per_layer"]][:15] == [
        "input_ms_per_step", "step_ms_p50", "device_ms_per_step",
        "device_idle", "peak_hbm", "attn_roofline", "step_mfu.lm",
        "ps_host_ms_per_step", "ps_program_load_s", "step_mfu.zaya",
        "moe_roofline", "attn_roofline.zaya", *READERS]
    tokens = [m for m in bm["end_to_end"]
              if m["name"] == "tokens_per_s_chip"][0]
    assert tokens["workloads"][:4] == [
        "gpt2-xl.t1024-b16", "zaya1-8b.t8192-b4", CELL, SMALL]
    old = {m["name"]: m for m in bm["per_layer"]}
    for name in ("attn_roofline", "step_mfu.lm"):
        assert old[name]["workloads"][:2] == ["gpt2-xl.t1024-b16", SMALL]
    for name in ("step_mfu.zaya", "moe_roofline", "attn_roofline.zaya"):
        assert old[name]["workloads"][:1] == ["zaya1-8b.t8192-b4"]
    assert [m["name"] for m in bm["end_to_end"]][:4] == [
        "samples_per_s_chip", "tokens_per_s_chip", "loss_at_n", "setup_s"]
    assert bm["run_seconds"] == 25


def test_the_configuration_holds_the_published_widths_and_its_cut():
    c = spec.load_cell(CELL).config
    published = {
        "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1,
        "head_dim": 64, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 7168, "kv_lora_rank": 512,
        "max_position_embeddings": 131072, "model_type": "joyai_llm_flash",
        "moe_intermediate_size": 768, "moe_layer_freq": 1, "n_group": 1,
        "n_shared_experts": 1, "norm_topk_prob": True,
        "num_attention_heads": 32, "num_experts_per_tok": 8,
        "num_key_value_heads": 32, "num_nextn_predict_layers": 1,
        "q_lora_rank": 1536, "qk_head_dim": 192, "qk_nope_head_dim": 128,
        "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
        "rope_interleave": True, "rope_scaling": None,
        "rope_theta": 32000000, "routed_scaling_factor": 2.5,
        "scoring_func": "sigmoid", "tie_word_embeddings": False,
        "topk_group": 1, "topk_method": "noaux_tc", "v_head_dim": 128}
    for key, value in published.items():
        assert c[key] == value, key
    assert c["reduced"] == ["num_hidden_layers", "n_routed_experts",
                            "vocab_size"]
    assert (c["num_hidden_layers"], c["n_routed_experts"],
            c["vocab_size"]) == (5, 8, 16160)
    assert c["published"] == {"num_hidden_layers": 40,
                              "n_routed_experts": 256, "vocab_size": 129280}
    assert c["vocab_size"] * 8 == c["published"]["vocab_size"]
    assert c["held_experts"] == [0, 8]
    assert c["router_bias_rate"] == 0.01 and c["mtp_loss_weight"] == 0.3
    assert "remat" not in c and "32 chips" in c["deployment"]
    for key in ("deployment", "precision", "departures", "assumed",
                "source"):
        assert c[key], key
    for form in ("mla", "router", "shared_expert", "mtp"):
        assert "[r]" in c["assumed"][form] and "[c]" in c["assumed"][form]


# ------------------------------------------------------- the cost functions
def test_parameter_counts_by_hand():
    """ISSUE 33's table: 491.7M by part."""
    p = costs_joyai.joyai_params(spec.load_cell(CELL).config)
    assert p["mla_matmul"] == 2048 * 1536 + 1536 * 32 * 192 + 2048 * 576 \
        + 512 * 32 * 256 + 32 * 128 * 2048 == 26345472
    assert p["mla"] == 26345472 + 1536 + 512
    assert p["mlp"] == 3 * 2048 * 7168 == 44040192
    assert p["dense_block"] == 26347520 + 4096 + 44040192 == 70391808
    assert p["router"] == 524288 and p["shared"] == p["expert"] == 4718592
    assert p["experts_held"] == 8 * 4718592 == 37748736
    assert p["expert_block"] == 26347520 + 4096 + 524288 + 4718592 \
        + 37748736 == 69343232
    assert p["embed"] == p["head"] == 16160 * 2048
    assert p["mtp"] == 8388608 + 4096 + 69343232 + 2048 == 77737984
    # what the program's tree holds (my compile, PR 33)
    assert p["total"] == 70391808 + 4 * 69343232 + 2 * 33095680 \
        + 77737984 + 2048 == 491696128


def test_flops_by_hand():
    c = spec.load_cell(CELL).config
    routed = 5 * 4096           # an even 1/32 of 131,072 in each of 5 layers
    assert costs_joyai.moe_flops_per_step(c, routed) == \
        6 * 3 * 2048 * 768 * routed
    # attention: 192 channels for q k^T, 128 for p v, 6 calls
    fwd = 2 * 32 * 2 * 8192 * 8192 * (192 + 128) / 2
    assert costs_joyai.attention_flops_per_step(c, 2, 8192) == 6 * 3 * fwd
    per_token = 6 * (6 * 26345472 + 44040192 + 5 * (524288 + 4718592)
                     + 2 * 2048 * 2048 + 16160 * 2048)
    want = 16384 * per_token + 6 * 16160 * 2048 * 2 * 8191 + 18 * fwd \
        + 6 * 3 * 2048 * 768 * routed
    assert costs_joyai.joyai_flops_per_step(c, 2, 8192, routed) == want
    assert 54.5e12 < want < 55.5e12
    assert 0.44 < 18 * fwd / want < 0.46        # the kernels' share
    # every choice on held experts: 18 TFLOP heavier, as ISSUE 33 reckons
    heavy = costs_joyai.joyai_flops_per_step(c, 2, 8192, 5 * 131072)
    assert 17e12 < heavy - want < 18.5e12
    assert costs_joyai.moe_bytes_per_step(c) == 9 * 5 * 8 * 2048 * 768 * 2


# --------------------------------------------------------------- the readers
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _fake_run(ops, routed=5 * 4096, peaks=PEAKS, steps=2):
    cell = spec.load_cell(CELL)
    tr = tracelib.Trace(devices={0: [tracelib.Op(*o) for o in ops]})
    info = {} if routed is None else {"routed_tokens_held": routed}
    return harness.Run(
        cell=cell, chips=1, config=cell.config, traffic=cell.traffic,
        peaks=peaks, info=info, trace=tr, traced_steps=steps, n_steps=24,
        window_s=25.0, trace_summary={"lo": 0.0, "hi": 10.0})


OPS = [
    # name, opcode, shapes, start, seconds
    ("ragged-dot-none.3", "custom-call",
     "bf16[16384,768] <- s32[1],s32[9],bf16[16384,2048],bf16[8,2048,768]",
     0.0, 0.002),
    ("ragged-dot-none.9", "custom-call",
     "f32[8,768,2048] <- s32[9],bf16[16384,768],bf16[16384,2048]",
     0.1, 0.004),
    ("while.5", "while",
     "f32[8,2048,768] <- s32[],f32[8,2048,768],bf16[16384,2048]", 0.0, 0.5),
    ("convert.2", "fusion", "bf16[8,2048,768] <- f32[491696128]", 0.6, 1.0),
    ("add.7", "fusion", "f32[8,2048,768] <- f32[8,2048,768],bf16[8,2048,768]",
     1.7, 0.1),
    ("fusion.7", "fusion", "bf16[16384,2048] <- bf16[16384,2048],s32[16384]",
     1.9, 1.0),
    ("flash_fwd.4", "custom-call", "bf16[2,32,8192,128] <- s32[1]", 3.0,
     0.05),
    ("flash_dkv.2", "custom-call", "bf16[2,32,8192,192] <- s32[1]", 3.1,
     0.05),
]


def test_moe_roofline_joyai_reads_the_ops_with_stack_and_window_rows():
    read = spec.load_reader("moe_roofline.joyai")
    c = spec.load_cell(CELL).config
    took = 0.006 / 2
    by_flops = costs_joyai.moe_flops_per_step(c, 5 * 4096) / 197e12
    by_bytes = costs_joyai.moe_bytes_per_step(c) / 819e9
    assert by_flops > by_bytes                  # 512 rows: past the ridge
    assert read(_fake_run(OPS)) == pytest.approx(100.0 * by_flops / took)
    # a light step is bound by the stacks' bytes, whatever was routed
    assert read(_fake_run(OPS, routed=64)) == pytest.approx(
        100.0 * by_bytes / took)
    assert read(_fake_run(OPS[2:])) is None     # loop, cast, sums: no rows
    assert read(_fake_run(OPS, routed=None)) is None
    assert read(_fake_run(OPS, peaks=None)) is None


def test_attn_roofline_joyai_reads_the_kernels_named_flash():
    read = spec.load_reader("attn_roofline.joyai")
    c = spec.load_cell(CELL).config
    want = 100.0 * costs_joyai.attention_flops_per_step(c, 2, 8192) \
        / 197e12 / (0.10 / 2)
    assert read(_fake_run(OPS)) == pytest.approx(want)
    assert read(_fake_run(OPS[:6])) is None


def test_step_mfu_joyai_counts_the_assignments_really_routed():
    read = spec.load_reader("step_mfu.joyai")
    c = spec.load_cell(CELL).config
    even = read(_fake_run(OPS))
    assert even == pytest.approx(
        100.0 * costs_joyai.joyai_flops_per_step(c, 2, 8192, 20480) * 0.96
        / 197e12)
    assert read(_fake_run(OPS, routed=0)) < even
    assert read(_fake_run(OPS, routed=None)) is None    # no observer
    assert read(_fake_run(OPS, peaks=None)) is None     # no chip


@pytest.mark.parametrize("name", READERS)
def test_a_reader_gives_nothing_without_a_trace_and_does_not_raise(name):
    """A program without the model has no observer, a run without
    ``--trace 1`` no trace: nothing to read is ``None``, never an error."""
    cell = spec.load_cell(CELL)
    bare = harness.Run(cell=cell, chips=1, config=cell.config,
                       traffic=cell.traffic, peaks=PEAKS, info={},
                       trace=None, traced_steps=0, n_steps=24,
                       window_s=25.0, trace_summary=None)
    assert spec.load_reader(name)(bare) is None


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
    assert set(line["metrics"]) >= {"setup_s", "samples_per_s_chip",
                                    "tokens_per_s_chip", "loss_at_n"}
    for row in line["check"].values():
        assert row["value"] <= row["limit"]
    assert line["compiles"]["window"]["cache_misses"] == 0
    assert "assignments of each held expert" in err     # the observer
    assert "lm.nll" in err and "mtp.nll" in err


def test_a_traced_tiny_run_reports_the_layers_a_cpu_can(root):
    """No chip, so no peaks: the three shares report nothing and do not
    raise; the metrics every training cell owes are there but those a CPU
    trace has no device plane for: the step's own account and stage
    records are, so ``ps_step_hbm`` and ``ps_program_trace_s`` print."""
    line, _ = _run(root, SEEDS[1], trace=True)
    assert {"input_ms_per_step", "step_ms_p50", "ps_host_ms_per_step",
            "ps_program_load_s", "ps_step_hbm",
            "ps_program_trace_s"} <= set(line["metrics"])
    assert not set(READERS) & set(line["metrics"])


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
    mod = spec.load_system("joyai")
    phases = harness.Phases(harness.process_start_time())
    system = mod.build(cell, seed, phases)
    prog = harness.first_readings(system)
    system.free()
    ref = system.reference()
    assert check.decide(prog, ref, cell.workload["limits"])[0]
    return mod, system, ref, cell.workload["limits"], phases


@pytest.mark.parametrize("seed", SEEDS)
def test_the_control_and_every_planted_fault_fail(root, seed):
    """The control (scaled fp8), half a batch, and each piece of the
    mathematics left out or done otherwise (the prediction module's loss,
    the shared expert, the bias in the choice, the gates' normalisation,
    the shared rotated key, the eighth expert): each not correct, on every
    seed; and the faults start from the sound reference's bias."""
    import numpy as np
    mod, system, ref, limits, phases = _readings(root, seed)
    assert set(mod.FAULTS) == {
        "fault_half_batch", "fault_no_mtp", "fault_no_shared",
        "fault_no_bias", "fault_raw_gates", "fault_k_rope_per_head",
        "fault_top_k_less_one"}
    assert ref["bias"].shape == (3, 8)
    for name, kw in mod.FAULTS.items():
        faulty = system.reference(**kw)
        np.testing.assert_array_equal(faulty["bias"], ref["bias"])
        ok, rows = check.decide(faulty, ref, limits)
        assert not ok, (name, rows)
    control = mod.control_readings(system, phases)
    assert all(v == v and abs(v) < 1e30 for v in control["loss"])  # finite
    ok, rows = check.decide(control, ref, limits)
    assert not ok, rows


def test_an_unknown_fault_is_refused():
    from benchlib.reference import joyai_ref
    with pytest.raises(ValueError, match="no fault"):
        joyai_ref.run({}, [], None, [], fault="other")
