"""The Olmo-Hybrid cell (``olmo-hybrid-7b.t8192-b4``, the benchmark's
four-chip cell): its files are found by name with ``chips`` 4, a tiny copy
runs whole through the harness on one and on four virtual devices and is
``correct``, the control and every planted fault come out not correct on
each seed, the exchange between chips left out is not correct, the cost
functions agree with counts made by hand, the four readers read what they
say and give nothing where there is nothing to read, and the accepted
entries keep their places (asserted as prefixes, so that the next addition
does not break this file)."""

import io
import json

import pytest

import tiny_olmo
from benchlib import check, costs_olmo, harness, spec
from benchlib import trace as tracelib

CELL = tiny_olmo.CELL
SEEDS = (3500000000, 3500007919, 3500015838)
READERS = ("step_mfu.olmo", "attn_roofline.olmo", "linattn_roofline.olmo",
           "collective_ms_per_step")
# the tiny size's own readings on the CPU (4 seeds, bench/tools/
# check_faults.py --root; loss / grad / delta): sound <= 4.0e-5 / 0.0109 /
# 0.0226; the control (fp8 matmul inputs, the state and the decay in
# bfloat16) grad >= 0.066, delta >= 0.049; the state reset every 64 tokens
# grad >= 0.072; every other fault grad >= 0.41
LIMITS = {"loss_step1": 2e-4, "loss_step2": 2e-4, "loss_step3": 2e-4,
          "grad_worst_leaf": 0.03, "delta_worst_leaf": 0.035}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_olmo.make_root(str(tmp_path_factory.mktemp("tiny_olmo")),
                               limits=LIMITS)


# ------------------------------------------------------------ the files
def test_the_cells_files_are_found_by_name_with_four_chips():
    cell = spec.load_cell(CELL)
    assert (cell.chips, cell.config_name, cell.traffic_name) == (
        4, "olmo-hybrid-7b", "packed-t8192-b4.v12544")
    assert cell.config["system"] == "olmo_hybrid"
    mix = {k: v for k, v in cell.traffic.items() if k != "assumed"}
    assert mix == {"kind": "lm_tokens", "batch": 4, "seq_len": 8192,
                   "vocab": 12544, "zipf_alpha": 1.05, "pool_batches": 16,
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
    assert per["linattn_roofline.olmo"]["layer"] == "linear attention"
    assert per["collective_ms_per_step"]["layer"] == "fused PS step"
    assert set(cell.workload["limits"]) == {
        "loss_step1", "loss_step2", "loss_step3", "grad_worst_leaf",
        "delta_worst_leaf"}
    assert cell.workload["loss_steps"] == [9, 24]
    assert callable(spec.load_system("olmo_hybrid").build)


def test_it_is_the_benchmarks_one_four_chip_cell():
    """The first of them: how many a benchmark may hold is
    ``test_benchmark_json.py``'s to say."""
    bm = spec.load_benchmark()
    assert [w["name"] for w in bm["workloads"]
            if w["chips"] == 4][:1] == [CELL]


def test_the_accepted_entries_keep_their_places_as_prefixes():
    bm = spec.load_benchmark()
    assert [c["name"] for c in bm["configs"]][:4] == [
        "gpt2-xl", "zaya1-8b", "joyai-llm-flash", "olmo-hybrid-7b"]
    assert [w["name"] for w in bm["workloads"]][:5] == [
        "gpt2-xl.t1024-b16", "zaya1-8b.t8192-b4", "joyai-llm-flash.t8192-b2",
        "gpt2-xl.t1024-b4", CELL]
    assert [m["name"] for m in bm["per_layer"]][15:19] == list(READERS)
    tokens = [m for m in bm["end_to_end"]
              if m["name"] == "tokens_per_s_chip"][0]
    assert tokens["workloads"][4] == CELL
    assert [m["name"] for m in bm["end_to_end"]][:4] == [
        "samples_per_s_chip", "tokens_per_s_chip", "loss_at_n", "setup_s"]
    assert bm["run_seconds"] == 25


# the catalog row's ``config`` (model-configs guide, architectures.jsonl,
# Olmo-Hybrid-7B), key by key; ``layer_types`` is three linear layers to a
# full one, eight times
PUBLISHED = {
    "model_type": "olmo_hybrid", "vocab_size": 100352, "hidden_size": 3840,
    "intermediate_size": 11008, "num_hidden_layers": 32,
    "num_attention_heads": 30, "num_key_value_heads": 30,
    "hidden_act": "silu", "max_position_embeddings": 65536,
    "attention_bias": False, "rms_norm_eps": 1e-06,
    "tie_word_embeddings": False,
    "layer_types": (["linear_attention"] * 3 + ["full_attention"]) * 8,
    "linear_num_key_heads": 30, "linear_num_value_heads": 30,
    "linear_key_head_dim": 96, "linear_value_head_dim": 192,
    "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
    "rope_parameters": {"rope_theta": None}}


def test_the_configuration_holds_the_published_keys_and_its_cut():
    c = spec.load_cell(CELL).config
    cut = {"num_hidden_layers": 4, "vocab_size": 12544}
    for key, value in PUBLISHED.items():
        assert c[key] == cut.get(key, value), key
    assert c["reduced"] == sorted(cut) == ["num_hidden_layers", "vocab_size"]
    assert c["published"] == {k: PUBLISHED[k] for k in cut}
    assert c["vocab_size"] * 8 == c["published"]["vocab_size"]
    entry = [e for e in spec.load_benchmark()["configs"]
             if e["name"] == "olmo-hybrid-7b"][0]
    assert entry["reduced"] == c["reduced"] and entry["source"] == c["source"]
    assert "remat" not in c and "four-chip host" in c["deployment"]
    assert "eight pipeline stages" in c["deployment"]
    for key in ("deployment", "precision", "departures", "assumed",
                "source"):
        assert c[key], key
    for form in ("linear_attention", "full_attention", "block"):
        assert "[r]" in c["assumed"][form] and "[c]" in c["assumed"][form]


# ------------------------------------------------------- the cost functions
def test_parameter_counts_by_hand():
    """ISSUE 35's table: 928.9M by part."""
    p = costs_olmo.olmo_params(spec.load_cell(CELL).config)
    assert p["linear_matmul"] == 2 * 3840 * 2880 + 3 * 3840 * 5760 \
        + 2 * 3840 * 30 == 88704000
    assert p["linear_mixer"] == 88704000 + 4 * (2880 + 2880 + 5760) \
        + 30 + 30 + 192 == 88750332
    assert p["full_matmul"] == 4 * 3840 * 3840 == 58982400
    assert p["full_mixer"] == 58982400 + 2 * 3840
    assert p["mlp"] == 3 * 3840 * 11008 == 126812160
    assert p["linear_block"] == 88750332 + 126812160 + 2 * 3840 == 215570172
    assert p["full_block"] == 58990080 + 126812160 + 7680 == 185809920
    assert p["embed"] == p["head"] == 12544 * 3840 == 48168960
    assert 3 * p["linear_block"] == 646710516        # the issue's 646.71M
    assert p["total"] == 646710516 + 185809920 + 2 * 48168960 + 3840 \
        == 928862196


def test_flops_and_bytes_by_hand():
    c = spec.load_cell(CELL).config
    f = costs_olmo.olmo_flops_per_step(c, 1, 8192)
    assert f["linear_proj"] == 6 * 3 * 88704000 * 8192
    assert f["full_proj"] == 6 * 58982400 * 8192
    assert f["mlp"] == 6 * 4 * 126812160 * 8192
    assert f["head"] == 6 * 48168960 * 8192
    # one full layer: q k^T and p v at 128 channels, halved by the mask
    assert f["attention"] == 3 * 30 * 2 * 8192 * 8192 * 256 / 2
    # the recurrence's count: 2 x (3 x 96 x 192) a token and head forward
    assert f["delta_rule"] == 3 * 3 * 2 * 3 * 96 * 192 * 30 * 8192
    assert f["total"] == sum(v for k, v in f.items() if k != "total")
    assert 45.0e12 < f["total"] < 45.2e12
    assert 0.28 < f["linear_proj"] / f["total"] < 0.30
    assert costs_olmo.olmo_flops_per_step(c, 4, 8192)["total"] \
        == pytest.approx(4 * f["total"])
    per_token = 30 * ((2 * 96 + 2 * 192) * 2 + 2 * 4)
    states = 128 * 30 * 96 * 192 * 4
    assert costs_olmo.delta_rule_bytes_per_step(c, 1, 8192) \
        == 3 * 2 * (8192 * per_token + states)
    # bound by bytes, not by the MXU
    assert costs_olmo.delta_rule_bytes_per_step(c, 1, 8192) / 819e9 \
        > costs_olmo.delta_rule_flops_per_step(c, 1, 8192) / 197e12


# --------------------------------------------------------------- the readers
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _fake_run(devices, peaks=PEAKS, steps=2, chips=4):
    cell = spec.load_cell(CELL)
    tr = tracelib.Trace(devices={
        d: [tracelib.Op(*o) for o in ops] for d, ops in devices.items()})
    return harness.Run(
        cell=cell, chips=chips, config=cell.config, traffic=cell.traffic,
        peaks=peaks, info={}, trace=tr, traced_steps=steps, n_steps=30,
        window_s=25.0, trace_summary={"lo": 0.0, "hi": 10.0})


OPS = [
    # name, opcode, shapes, start, seconds
    ("fusion.3", "fusion",
     "f32[128,1,30,64,288] <- f32[128,30,64,64],f32[128,1,30,64,192]",
     0.0, 0.010),
    ("custom-call.4", "custom-call",
     "f32[128,1,30,1,64,64] <- f32[128,1,30,1,64,64]", 0.1, 0.020),
    ("while.5", "while",
     "s32[] f32[1,30,96,192] f32[128,1,30,64,96] <- s32[]", 0.2, 0.030),
    ("fusion.9", "fusion", "f32[1,30,96,192] <- f32[1,30,96,192],f32[30]",
     0.21, 0.010),                              # inside the loop: once
    ("fusion.11", "fusion", "f32[1,8192,30,96] <- bf16[1,8192,2880]", 0.5,
     1.0),                                      # the convolution: no chunk
    ("fusion.12", "fusion", "bf16[8192,11008] <- bf16[8192,3840]", 1.6,
     1.0),                                      # the MLP
    ("flash_fwd.4", "custom-call", "bf16[1,30,8192,128] <- s32[1]", 3.0,
     0.04),
    ("flash_bwd.2", "custom-call", "bf16[1,30,8192,128] <- s32[1]", 3.1,
     0.06),
    ("all-reduce.99", "all-reduce", "bf16[928862196] <- bf16[928862196]",
     4.0, 0.05),
    ("all-reduce-start.1", "all-reduce-start", "f32[928862196] <- f32[9]",
     5.0, 0.01),
    ("all-reduce-done.1", "all-reduce-done", "f32[928862196] <- f32[9]",
     5.2, 0.04),
]


def test_linattn_roofline_reads_the_ops_that_carry_the_chunked_shapes():
    read = spec.load_reader("linattn_roofline.olmo")
    c = spec.load_cell(CELL).config
    took = 0.060 / 2                # the loop holds its body: a union
    by_bytes = costs_olmo.delta_rule_bytes_per_step(c, 1, 8192) / 819e9
    assert read(_fake_run({0: OPS})) == pytest.approx(
        100.0 * by_bytes / took)
    # averaged over the chips
    assert read(_fake_run({0: OPS, 1: OPS[:2]})) == pytest.approx(
        100.0 * by_bytes / ((0.060 + 0.030) / 2 / 2))
    assert read(_fake_run({0: OPS[4:]})) is None    # no chunked shape
    assert read(_fake_run({0: OPS}, peaks=None)) is None


@pytest.mark.parametrize("detail, carries", [
    ("f32[128,1,30,64,96] <- bf16[1,128,64,30,96]", True),
    ("f32[1,30,64,64] <- f32[1,30,64,96]", True),
    ("f32[128,1,30,96,192] <- f32[1,30,96,192]", True),     # the states
    ("f32[1,8192,30,192] <- bf16[1,8192,5760]", False),     # before chunks
    ("bf16[1,30,8192,128] <- s32[1]", False),               # the kernels
    ("bf16[8192,11008] <- bf16[8192,3840]", False),
    ("f32[64,96] <- f32[64,192]", False)])                  # no heads
def test_which_shapes_are_the_chunked_forms(detail, carries):
    import importlib.util
    import os
    path = os.path.join(spec.BENCH_DIR, "metrics", "linattn_roofline.olmo.py")
    s = importlib.util.spec_from_file_location("linattn_reader", path)
    mod = importlib.util.module_from_spec(s)
    s.loader.exec_module(mod)
    assert mod.carries_chunked_shape(detail, 30, 96, 192) is carries


def test_attn_roofline_olmo_reads_the_kernels_named_flash():
    read = spec.load_reader("attn_roofline.olmo")
    c = spec.load_cell(CELL).config
    want = 100.0 * costs_olmo.attention_flops_per_step(c, 1, 8192) \
        / 197e12 / (0.10 / 2)
    assert read(_fake_run({0: OPS})) == pytest.approx(want)
    assert read(_fake_run({0: OPS[:6]})) is None


def test_step_mfu_olmo_is_a_chips_share_of_the_batch_over_the_peak():
    read = spec.load_reader("step_mfu.olmo")
    c = spec.load_cell(CELL).config
    want = 100.0 * costs_olmo.olmo_flops_per_step(c, 1, 8192)["total"] \
        * 30 / 25.0 / 197e12
    assert read(_fake_run({0: OPS})) == pytest.approx(want)
    assert read(_fake_run({0: OPS}, peaks=None)) is None    # no chip


def test_collective_ms_is_the_busiest_chips():
    read = spec.load_reader("collective_ms_per_step")
    quiet = [o for o in OPS if "all-reduce" not in o[0]]
    assert read(_fake_run({0: OPS, 1: OPS[:9], 2: quiet})) == pytest.approx(
        1e3 * 0.10 / 2)
    assert read(_fake_run({0: quiet})) is None      # one chip: none built


@pytest.mark.parametrize("name", READERS)
def test_a_reader_gives_nothing_without_a_trace_and_does_not_raise(name):
    """A run without ``--trace 1`` has no trace, a CPU run no peaks:
    nothing to read is ``None``, never an error."""
    cell = spec.load_cell(CELL)
    bare = harness.Run(cell=cell, chips=4, config=cell.config,
                       traffic=cell.traffic, peaks=None, info={},
                       trace=None, traced_steps=0, n_steps=30,
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
    assert "by linear layer: mean decay" in err         # the observer
    assert "lm.nll" in err


def test_a_traced_tiny_run_reports_the_layers_a_cpu_can(root):
    """No chip, so no peaks and no device plane: the four new readers
    report nothing and do not raise; the metrics every training cell owes
    are there but those a CPU trace has no device plane for: the step's
    own account and stage records are, so ``ps_step_hbm`` and
    ``ps_program_trace_s`` print."""
    line, _ = _run(root, SEEDS[1], trace=True)
    assert {"input_ms_per_step", "step_ms_p50", "ps_host_ms_per_step",
            "ps_program_load_s", "ps_step_hbm",
            "ps_program_trace_s"} <= set(line["metrics"])
    assert not set(READERS) & set(line["metrics"])


def test_the_tiny_cell_on_four_devices_and_its_exchange_left_out(
        tmp_path):
    """The cell as it is deployed, on four (virtual) devices: ``correct``
    under the same limits; with every chip fed chip 0's sequence the
    step's mean is what chip 0 alone computes with no exchange, and that
    is not."""
    import numpy as np
    root4 = tiny_olmo.make_root(str(tmp_path), limits=LIMITS, chips=4)
    assert spec.load_cell(CELL, root4).chips == 4
    line, _ = _run(root4, SEEDS[2])
    assert line["correct"] is True and line["device"]["count"] == 4

    def wrap(system):
        put = system.put
        system.put = lambda b: put({
            k: np.concatenate([v[: v.shape[0] // 4]] * 4)
            for k, v in b.items()})
        return system
    line, _ = _run(root4, SEEDS[2], wrap=wrap)
    assert line["correct"] is False
    g = line["check"]["grad_worst_leaf"]
    assert g["value"] > 3 * g["limit"]


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
    """The control (fp8 matmul inputs, the state and the decay in
    bfloat16), a quarter of the batch (the exchange left out) and each
    piece of the mathematics left out or done otherwise: each not
    correct, on every seed."""
    cell = spec.load_cell(CELL, root)
    mod = spec.load_system("olmo_hybrid")
    phases = harness.Phases(harness.process_start_time())
    system = mod.build(cell, seed, phases)
    prog = harness.first_readings(system)
    system.free()
    ref = system.reference()
    limits = cell.workload["limits"]
    assert check.decide(prog, ref, limits)[0]
    assert set(mod.FAULTS) == {
        "fault_no_exchange", "fault_beta_not_doubled", "fault_no_decay",
        "fault_state_reset_every_chunk", "fault_no_conv",
        "fault_qk_not_normalised", "fault_no_gate", "fault_full_as_linear"}
    for name, kw in mod.FAULTS.items():
        ok, rows = check.decide(system.reference(**kw), ref, limits)
        assert not ok, (name, rows)
    control = mod.control_readings(system, phases)
    assert all(v == v and abs(v) < 1e30 for v in control["loss"])  # finite
    ok, rows = check.decide(control, ref, limits)
    assert not ok, rows


def test_the_state_alone_in_bfloat16_is_a_reading_of_its_own(root):
    """``low="state"``: the state and the decay in bfloat16 and nothing
    else below the sound reference: finite, not the sound reference's
    numbers, and nearer to them than the whole control's."""
    cell = spec.load_cell(CELL, root)
    mod = spec.load_system("olmo_hybrid")
    system = mod.build(cell, SEEDS[0],
                       harness.Phases(harness.process_start_time()))
    system.free()
    ref = system.reference()
    state = system.reference(low="state")
    control = system.reference(low=True)
    assert state["loss"] != ref["loss"]
    gap = lambda r: check.numbers(r, ref)["loss_step1"][0]   # noqa: E731
    assert 0.0 < gap(state) < gap(control) < 1.0


def test_the_weights_are_made_laid_over_the_cells_devices(tmp_path):
    """On four devices no matrix is made whole on one (the tree, its ravel
    and the table's padded copy on one chip were the run's peak), and the
    values are those one device makes, bit for bit."""
    import jax
    import numpy as np
    root4 = tiny_olmo.make_root(str(tmp_path), limits=LIMITS, chips=4)
    cell = spec.load_cell(CELL, root4)
    mod = spec.load_system("olmo_hybrid")
    system = mod.build(cell, SEEDS[1],
                       harness.Phases(harness.process_start_time()))
    made = system._make(system._keys)
    for name, leaf in zip(system.names, jax.tree.leaves(made)):
        if leaf.ndim > 1:
            shard = leaf.addressable_shards[0].data
            assert shard.size * 4 == leaf.size, name
    one = jax.jit(lambda k: mod.make_params(
        system.struct, system.names, system.config, k, jax.numpy))(
            system._keys)
    for a, b in zip(jax.tree.leaves(made), jax.tree.leaves(one)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    flat = np.concatenate([np.asarray(x).reshape(-1)
                           for x in jax.tree.leaves(one)])
    np.testing.assert_array_equal(
        np.asarray(system.table.params)[: flat.size], flat)


def test_an_unknown_fault_is_refused():
    from benchlib.reference import olmo_hybrid_ref
    with pytest.raises(ValueError, match="no fault"):
        olmo_hybrid_ref.run({}, [], None, [], fault="other")
