"""The Laguna cell (``laguna-s-2.1.t16384-b1``): its files are found by
name, the configuration holds the published widths and its cut, the
accepted entries keep their places (asserted as prefixes, so that the next
addition does not break this file), the cost functions agree with counts
made by hand, the three readers read what they say and give nothing where
there is nothing to read, a tiny copy runs whole through the harness and
is ``correct``, and the control and every planted fault come out not
correct on each seed."""

import io
import json

import pytest

import tiny_laguna
from benchlib import check, costs_laguna, harness, spec
from benchlib import trace as tracelib

CELL = tiny_laguna.CELL
SEEDS = (3300000000, 3300015838, 3300023757)
READERS = ("step_mfu.laguna", "window_roofline.laguna",
           "moe_roofline.laguna")


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_laguna.make_root(str(tmp_path_factory.mktemp("tiny_laguna")))


# ------------------------------------------------------------ the files
def test_the_cells_files_are_found_by_name():
    cell = spec.load_cell(CELL)
    assert (cell.chips, cell.config_name, cell.traffic_name) == (
        1, "laguna-s-2.1", "packed-t16384-b1.v12544")
    assert cell.config["system"] == "laguna"
    mix = {k: v for k, v in cell.traffic.items() if k != "assumed"}
    assert mix == {"kind": "lm_tokens", "batch": 1, "seq_len": 16384,
                   "vocab": 12544, "zipf_alpha": 1.05, "pool_batches": 16,
                   "warmup_steps": 2, "trace_seconds": 4.0}
    assert {m["name"] for m in cell.end_to_end} >= {
        "samples_per_s_chip", "tokens_per_s_chip", "loss_at_n", "setup_s"}
    # at least the per-layer metrics due in every cell and the cell's three
    # readers: a metric appended without a ``workloads`` list is due here
    # too
    per = {m["name"]: m for m in cell.per_layer}
    assert set(per) >= {
        "input_ms_per_step", "step_ms_p50", "device_ms_per_step",
        "device_idle", "peak_hbm", "ps_host_ms_per_step",
        "ps_program_load_s", *READERS}
    for name in READERS:
        assert per[name]["workloads"][:1] == [CELL]
        assert per[name]["moves"] == "tokens_per_s_chip"
        assert callable(spec.load_reader(name))
    # loss_step1 is left out: the control reads like a sound run there
    assert set(cell.workload["limits"]) == {
        "loss_step2", "loss_step3", "grad_worst_leaf", "delta_worst_leaf"}
    assert cell.workload["loss_steps"] == [9, 24]
    assert callable(spec.load_system("laguna").build)


def test_the_accepted_entries_keep_their_places_as_prefixes():
    bm = spec.load_benchmark()
    assert [c["name"] for c in bm["configs"]][:5] == [
        "gpt2-xl", "zaya1-8b", "joyai-llm-flash", "olmo-hybrid-7b",
        "laguna-s-2.1"]
    assert [w["name"] for w in bm["workloads"]][:6] == [
        "gpt2-xl.t1024-b16", "zaya1-8b.t8192-b4", "joyai-llm-flash.t8192-b2",
        "gpt2-xl.t1024-b4", "olmo-hybrid-7b.t8192-b4", CELL]
    names = [m["name"] for m in bm["per_layer"]]
    assert names[:3] == ["input_ms_per_step", "step_ms_p50",
                         "device_ms_per_step"]
    assert names[24:26] == ["ps_step_hbm", "ps_program_trace_s"]
    tokens = [m for m in bm["end_to_end"]
              if m["name"] == "tokens_per_s_chip"][0]
    assert tokens["workloads"][:6] == [
        "gpt2-xl.t1024-b16", "zaya1-8b.t8192-b4", "joyai-llm-flash.t8192-b2",
        "gpt2-xl.t1024-b4", "olmo-hybrid-7b.t8192-b4", CELL]
    assert [m["name"] for m in bm["end_to_end"]][:4] == [
        "samples_per_s_chip", "tokens_per_s_chip", "loss_at_n", "setup_s"]
    assert bm["run_seconds"] == 25
    assert [w["name"] for w in bm["workloads"] if w["chips"] == 4][:1] == [
        "olmo-hybrid-7b.t8192-b4"]
    assert names[26:29] == list(READERS)


def test_the_three_readers_are_listed_with_their_fields():
    """Appended after PR 37's seven, each due in this cell (the first of
    its ``workloads``) and moving its rate; the files are the readers PR
    41 brought."""
    per = {m["name"]: m for m in spec.load_benchmark()["per_layer"]}
    rows = [(per[n]["unit"], per[n]["better"], per[n]["source"],
             per[n]["layer"], per[n]["moves"], per[n]["workloads"][:1])
            for n in READERS]
    due = ("tokens_per_s_chip", [CELL])
    assert rows == [("%", "higher", "host_clock", "whole step") + due,
                    ("%", "higher", "device_trace", "kernels") + due,
                    ("%", "higher", "device_trace", "expert layer") + due]


def test_the_configuration_holds_the_published_widths_and_its_cut():
    c = spec.load_cell(CELL).config
    published = {
        "model_type": "laguna", "hidden_size": 3072,
        "intermediate_size": 12288, "head_dim": 128,
        "max_position_embeddings": 1048576, "attention_bias": False,
        "rms_norm_eps": 1e-06, "num_experts_per_tok": 10,
        "moe_intermediate_size": 1024,
        "shared_expert_intermediate_size": 1024, "norm_topk_prob": True,
        "decoder_sparse_step": 1, "mlp_only_layers": [0],
        "tie_word_embeddings": False, "gating": "per-head",
        "sliding_window": 512, "moe_apply_router_weight_on_input": False,
        "moe_routed_scaling_factor": 2.5, "moe_router_logit_softcapping": 0}
    for key, value in published.items():
        assert c[key] == value, key
    rope = c["rope_parameters"]
    assert rope["full_attention"] == {
        "rope_theta": 500000, "rope_type": "yarn", "factor": 128,
        "original_max_position_embeddings": 8192, "beta_slow": 1,
        "beta_fast": 32, "attention_factor": 1.4852030263919618,
        "partial_rotary_factor": 0.5}
    assert rope["sliding_attention"] == {
        "rope_type": "default", "rope_theta": 10000,
        "partial_rotary_factor": 1}
    kinds = ["full_attention"] + ["sliding_attention"] * 3
    assert c["layer_types"] == kinds * 12
    assert c["mlp_layer_types"] == ["dense"] + ["sparse"] * 47
    assert c["gating_types"] == ["per_head"] * 48
    assert c["reduced"] == [
        "num_hidden_layers", "num_experts", "vocab_size",
        "num_key_value_heads", "num_attention_heads",
        "num_attention_heads_per_layer"]
    assert (c["num_hidden_layers"], c["num_experts"], c["vocab_size"],
            c["num_key_value_heads"], c["num_attention_heads"]) == (
        5, 8, 12544, 4, 24)
    pub = c["published"]
    assert (pub["num_hidden_layers"], pub["num_experts"], pub["vocab_size"],
            pub["num_key_value_heads"], pub["num_attention_heads"]) == (
        48, 256, 100352, 8, 48)
    assert pub["num_attention_heads_per_layer"] == [48, 72, 72, 72] * 12
    assert c["num_attention_heads_per_layer"] == [
        h // 2 for h in pub["num_attention_heads_per_layer"]]
    assert c["vocab_size"] * 8 == pub["vocab_size"]
    assert c["held_experts"] == [0, 8] and c["lr"] == 3e-6
    assert "remat" not in c and "32 chips" in c["deployment"]
    for key in ("deployment", "precision", "departures", "assumed",
                "source"):
        assert c[key], key
    for form in ("router", "shared_expert", "gate", "attention", "rotary"):
        assert "[c]" in c["assumed"][form] and "[a]" in c["assumed"][form]


# ------------------------------------------------------- the cost functions
def test_parameter_counts_by_hand():
    """ISSUE 41's table, heads shared two ways: 672,125,952."""
    p = costs_laguna.laguna_params(spec.load_cell(CELL).config)
    full = 3072 * 24 * 128 * 2 + 3072 * 4 * 128 * 2 + 3072 * 24
    win = 3072 * 36 * 128 * 2 + 3072 * 4 * 128 * 2 + 3072 * 36
    assert [x["attn"] for x in p["layers"]] == [full, win, win, win, full]
    assert p["mlp"] == 3 * 3072 * 12288 == 113246208
    assert p["router"] == 3072 * 256 and p["shared"] == p["expert"] \
        == 3 * 3072 * 1024
    assert [x["block"] for x in p["layers"]] == [
        135346176, 117295104, 117295104, 117295104, 107821056]
    assert p["embed"] == p["head"] == 12544 * 3072
    assert p["total"] == 672125952 == 135346176 + 3 * 117295104 \
        + 107821056 + 2 * 38535168 + 3072


def test_flops_by_hand():
    c = spec.load_cell(CELL).config
    T = 16384
    assert costs_laguna.visible_keys(T, None) == T * (T + 1) // 2
    assert costs_laguna.visible_keys(T, 512) == 512 * 513 // 2 \
        + (T - 512) * 512
    assert costs_laguna.visible_keys(8, 512) == 36     # window past T
    full = 2 * 3 * 24 * 4 * 128 * (T * (T + 1) // 2)
    win = 3 * 3 * 36 * 4 * 128 * (512 * 513 // 2 + (T - 512) * 512)
    assert costs_laguna.attention_flops_per_step(c, 1, T) == full + win
    assert costs_laguna.attention_flops_per_step(
        c, 1, T, "sliding_attention") == win
    # per token and forward: a full layer's attention 100.7 MFLOP, a
    # window layer's 9.3; the model 916 MFLOP: ISSUE 41's reckoning
    assert full / 3 / 2 / T == pytest.approx(100.67e6, rel=1e-4)
    assert win / 3 / 3 / T == pytest.approx(9.29e6, rel=1e-3)
    even = 4 * T * 10 * 8 // 256                # 640 an expert, 4 layers
    step = costs_laguna.laguna_flops_per_step(c, 1, T, even)
    assert step / 3 / T == pytest.approx(915.94e6, rel=1e-4)
    assert 44.5e12 < step < 45.5e12
    assert costs_laguna.moe_flops_per_step(c, even) == \
        6 * 3 * 3072 * 1024 * even
    assert costs_laguna.moe_bytes_per_step(c) == 9 * 4 * 8 * 3072 * 1024 * 2
    assert costs_laguna.window_bytes_per_step(c, 1, T) == 3 * T * (
        4 * 36 * 128 * 2 + 36 * 4 + 4 * 4 * 128 * 2)


# --------------------------------------------------------------- the readers
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
EVEN = 4 * 8 * 640          # 640 an expert held, 8 held, 4 layers


def _fake_run(ops, routed=EVEN, peaks=PEAKS, steps=2):
    cell = spec.load_cell(CELL)
    tr = tracelib.Trace(devices={0: [tracelib.Op(*o) for o in ops]})
    info = {} if routed is None else {"routed_tokens_held": routed}
    return harness.Run(
        cell=cell, chips=1, config=cell.config, traffic=cell.traffic,
        peaks=peaks, info=info, trace=tr, traced_steps=steps, n_steps=40,
        window_s=25.0, trace_summary={"lo": 0.0, "hi": 10.0})


OPS = [
    # name, opcode, shapes, start, seconds
    ("ragged-dot-none.3", "custom-call",
     "bf16[16384,1024] <- s32[1],s32[9],bf16[16384,3072],bf16[8,3072,1024]",
     0.0, 0.002),
    ("ragged-dot-none.9", "custom-call",
     "f32[8,1024,3072] <- s32[9],bf16[16384,1024],bf16[16384,3072]",
     0.1, 0.004),
    ("while.5", "while",
     "f32[8,3072,1024] <- s32[],f32[8,3072,1024],bf16[16384,3072]", 0.0,
     0.5),
    ("convert.2", "fusion", "bf16[8,3072,1024] <- f32[8,3072,1024]", 0.6,
     1.0),
    ("flash_fwd_win.4", "custom-call", "bf16[1,36,16384,128] <- s32[1]", 3.0,
     0.01),
    ("flash_bwd_win.2", "custom-call", "bf16[1,36,16384,128] <- s32[1]", 3.1,
     0.03),
    ("flash_fwd.1", "custom-call", "bf16[1,24,16384,128] <- s32[1]", 3.5,
     0.05),
    ("flash_bwd.7", "custom-call", "bf16[1,24,16384,128] <- s32[1]", 3.6,
     0.05),
]


def test_window_roofline_reads_the_window_kernels_alone():
    read = spec.load_reader("window_roofline.laguna")
    c = spec.load_cell(CELL).config
    flops = costs_laguna.attention_flops_per_step(c, 1, 16384,
                                                  "sliding_attention")
    need = flops / 197e12
    assert need > costs_laguna.window_bytes_per_step(c, 1, 16384) / 819e9
    assert read(_fake_run(OPS)) == pytest.approx(100.0 * need / (0.04 / 2))
    # bound by bytes where the peak of FLOPs is far above the bandwidth
    slow = {"bf16_flops_per_s": 1e18, "hbm_bytes_per_s": 819e9}
    assert read(_fake_run(OPS, peaks=slow)) == pytest.approx(
        100.0 * costs_laguna.window_bytes_per_step(c, 1, 16384) / 819e9
        / 0.02)
    assert read(_fake_run(OPS[:4] + OPS[6:])) is None   # full layers' only
    assert read(_fake_run(OPS, peaks=None)) is None


def test_moe_roofline_reads_the_ops_with_stack_and_window_rows():
    read = spec.load_reader("moe_roofline.laguna")
    c = spec.load_cell(CELL).config
    took = 0.006 / 2
    by_flops = costs_laguna.moe_flops_per_step(c, EVEN) / 197e12
    by_bytes = costs_laguna.moe_bytes_per_step(c) / 819e9
    assert by_flops > by_bytes                  # 640 rows: past the ridge
    assert read(_fake_run(OPS)) == pytest.approx(100.0 * by_flops / took)
    assert read(_fake_run(OPS, routed=64)) == pytest.approx(
        100.0 * by_bytes / took)
    assert read(_fake_run(OPS[2:])) is None     # loop, cast: no rows
    assert read(_fake_run(OPS, routed=None)) is None


def test_step_mfu_counts_the_assignments_really_routed():
    read = spec.load_reader("step_mfu.laguna")
    c = spec.load_cell(CELL).config
    even = read(_fake_run(OPS))
    assert even == pytest.approx(
        100.0 * costs_laguna.laguna_flops_per_step(c, 1, 16384, EVEN) * 1.6
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
                       trace=None, traced_steps=0, n_steps=40,
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
    assert "output gate's mean by layer" in err and "lm.nll" in err


@pytest.fixture(scope="module")
def traced(root):
    """One traced run of the tiny copy, and what each reader handed the
    harness in it."""
    got, load = {}, spec.load_reader

    def recording(name, bench_dir=spec.BENCH_DIR):
        read = load(name, bench_dir)

        def record(run):
            got[name] = read(run)
            return got[name]
        return record

    spec.load_reader = recording
    try:
        line, _ = _run(root, SEEDS[1], trace=True)
    finally:
        spec.load_reader = load
    return line, got


def test_a_traced_tiny_run_reports_the_layers_a_cpu_can(traced):
    """No chip, so no peaks: the three shares report nothing and do not
    raise; the metrics every training cell owes are there but those a CPU
    trace has no device plane for: the step's own account and stage
    records are, so ``ps_step_hbm`` and ``ps_program_trace_s`` print."""
    line, _ = traced
    assert {"input_ms_per_step", "step_ms_p50", "ps_host_ms_per_step",
            "ps_program_load_s", "ps_step_hbm",
            "ps_program_trace_s"} <= set(line["metrics"])
    assert not set(READERS) & set(line["metrics"])


def test_on_a_tiny_traced_run_the_three_read_nothing_or_a_share(traced):
    """What each of the three hands the harness on a whole traced run on
    the CPU: ``None`` (no chip, no peaks, no device plane) or a share the
    chip can reach, never one past 105%."""
    _, got = traced
    assert set(READERS) <= set(got)
    for name in READERS:
        assert got[name] is None or 0.0 <= got[name] <= 105.0, (
            name, got[name])


def test_an_unchanged_state_is_not_correct(root):
    def wrap(system):
        import jax.numpy as jnp
        system.step = lambda batch: jnp.float32(0.5)
        return system
    line, _ = _run(root, SEEDS[2], wrap=wrap)
    assert line["correct"] is False
    assert line["check"]["delta_worst_leaf"]["value"] == pytest.approx(
        1.0, abs=1e-3)


@pytest.mark.parametrize("seed", SEEDS)
def test_the_control_and_every_planted_fault_fail(root, seed):
    """The control (scaled fp8, bfloat16 residual), half a batch, and each
    piece of the mathematics left out or done otherwise (the per-head
    gate, the window, YaRN on the full layers, the shared expert, the
    gates' normalisation, the tenth expert): each not correct, on every
    seed."""
    cell = spec.load_cell(CELL, root)
    mod = spec.load_system("laguna")
    phases = harness.Phases(harness.process_start_time())
    system = mod.build(cell, seed, phases)
    prog = harness.first_readings(system)
    system.free()
    ref = system.reference()
    limits = cell.workload["limits"]
    assert check.decide(prog, ref, limits)[0]
    assert set(mod.FAULTS) == {
        "fault_half_batch", "fault_no_gate", "fault_window_wide",
        "fault_no_yarn", "fault_no_shared", "fault_raw_gates",
        "fault_top_k_less_one"}
    for name, kw in mod.FAULTS.items():
        ok, rows = check.decide(system.reference(**kw), ref, limits)
        assert not ok, (name, rows)
    control = mod.control_readings(system, phases)
    assert all(v == v and abs(v) < 1e30 for v in control["loss"])  # finite
    ok, rows = check.decide(control, ref, limits)
    assert not ok, rows


def test_an_unknown_fault_is_refused():
    from benchlib.reference import laguna_ref
    with pytest.raises(ValueError, match="no fault"):
        laguna_ref.run({}, [], None, [], fault="other")
