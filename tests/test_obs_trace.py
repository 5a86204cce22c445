"""Observability layer (minips_tpu/obs/): wire tracing, latency
histograms, cross-rank merge, blocked-time attribution — plus the
satellite fixes this PR rides in (MetricsLogger thread safety,
CommTimers snapshot aggregation, the done-line schema pin).

Fast tier: unit tests on the histogram math, the tracer ring, the
merge/report tools on synthesized traces, and in-process 2-rank drills
(threads as nodes, the repo's standard trick). Slow tier: 3-proc
launcher runs with MINIPS_TRACE armed — the acceptance drills (merged
trace with one client-pull→owner-serve flow pair per remote owner;
retransmit spans under seeded chaos; rebalance fence spans; the
traced-vs-untraced bitwise BSP drill lives in the fast tier since it
runs in-process)."""

from __future__ import annotations

import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from minips_tpu import launch
from minips_tpu.obs import tracer as trc
from minips_tpu.obs.hist import (Log2Histogram, merge_counts,
                                 quantile_us, summarize_counts)
from minips_tpu.obs.merge import (estimate_offsets_us, main as merge_main,
                                  merge_traces)
from minips_tpu.obs.report import attribute, format_table
from minips_tpu.train.sharded_ps import ShardedPSTrainer, ShardedTable
from minips_tpu.utils.metrics import MetricsLogger, wire_record
from minips_tpu.obs.comm_timers import CommTimers
from tests.conftest import mk_loopback_buses


@pytest.fixture(autouse=True)
def _tracer_isolation(monkeypatch):
    """Every test starts with the tracer DISARMED and leaves it so —
    the global handle must never leak between tests (or into the rest
    of the suite)."""
    monkeypatch.delenv("MINIPS_TRACE", raising=False)
    trc.reset_for_tests()
    yield
    trc.reset_for_tests()


# ---------------------------------------------------------------- hist


def test_log2_hist_buckets_and_quantiles():
    h = Log2Histogram()
    # bucket boundaries: [0,1) -> 0, [1,2) -> 1, [2,4) -> 2, [4,8) -> 3
    assert h.bucket_of(0.0) == 0 and h.bucket_of(0.99) == 0
    assert h.bucket_of(1.0) == 1 and h.bucket_of(1.99) == 1
    assert h.bucket_of(2.0) == 2 and h.bucket_of(3.99) == 2
    assert h.bucket_of(4.0) == 3
    # 50 fast samples (~1ms) + 50 slow (~100ms): the median sits in the
    # 1ms decade, p99 in the 100ms decade — the tail a mean would hide
    for _ in range(50):
        h.record_s(0.001)
    for _ in range(50):
        h.record_s(0.100)
    s = h.summary()
    assert s["count"] == 100
    assert 0.5 <= s["p50_ms"] <= 2.1
    assert 64.0 <= s["p99_ms"] <= 262.0
    # a mean of the same data is ~50ms — nowhere near either mode
    assert s["p50_ms"] < 25.0 < s["p99_ms"]


def test_hist_idle_summary_and_merge():
    assert Log2Histogram().summary() == {"count": 0}  # idle, not None
    a, b = Log2Histogram(), Log2Histogram()
    a.record_us(10.0)
    b.record_us(10.0)
    b.record_us(1000.0)
    merged = merge_counts([a.snapshot(), b.snapshot()])
    assert sum(merged) == 3
    assert summarize_counts(merged)["count"] == 3
    # fixed buckets: merging is exact, the quantile sees all 3 samples
    assert quantile_us(merged, 0.5) <= 16.0


def test_hist_thread_safety_total_count():
    h = Log2Histogram()

    def hammer():
        for _ in range(2000):
            h.record_us(7.0)
    ths = [threading.Thread(target=hammer) for _ in range(4)]
    for t in ths:
        t.start()
    for t in ths:
        t.join()
    assert sum(h.snapshot()) == 8000


# ---------------------------------------------------- CommTimers (satellite)


def test_commtimers_summary_quantiles_next_to_means():
    t = CommTimers()
    for ms in (1, 1, 1, 1, 50):
        t.record_pull(latency_s=ms / 1e3, blocked_s=ms / 2e3)
    t.record_push_ack(0.002)
    s = t.summary()
    # the means are still there, the quantiles ride next to them
    assert s["pull_latency_ms_mean"] is not None
    assert s["pull_latency_ms_p50"] is not None
    assert s["pull_latency_ms_p50"] < s["pull_latency_ms_p99"]
    assert s["push_ack_ms_p50"] is not None
    assert s["pull_blocked_ms_p95"] is not None


def test_commtimers_aggregate_merges_snapshots():
    a, b = CommTimers(), CommTimers()
    a.record_pull(0.001, 0.0005)
    b.record_pull(0.004, 0.001)
    b.record_pull_rows(requested=10, wire=4, hits=2, lookups=6)
    agg = CommTimers.aggregate([a, b])
    assert agg["pulls"] == 2
    assert agg["pull_rows_requested"] == 10
    assert agg["cache_hit_rate"] == round(2 / 6, 4)
    # histogram counts merged too
    assert agg["pull_latency_ms_p50"] is not None


def test_commtimers_aggregate_consistent_under_concurrent_mutation():
    """The satellite regression: aggregate() snapshots each timer under
    ONE lock acquisition instead of reaching into live fields one lock
    at a time — under concurrent recording every aggregate must be
    internally consistent (hist count == pulls count) and the final
    one exact."""
    timers = [CommTimers() for _ in range(3)]
    stop = threading.Event()
    recorded = [0] * 3

    def hammer(i):
        while not stop.is_set():
            timers[i].record_pull(0.001, 0.0005)
            recorded[i] += 1
    ths = [threading.Thread(target=hammer, args=(i,)) for i in range(3)]
    for t in ths:
        t.start()
    try:
        for _ in range(50):
            agg = CommTimers.aggregate(timers)
            snap = CommTimers.merge_snapshots(
                [t.snapshot() for t in timers])
            # a torn read would desync the sum-based and hist-based
            # counts; a snapshot can never
            assert agg["pulls"] >= 0
            assert sum(snap["hists"]["pull_latency"]) == snap["pulls"]
    finally:
        stop.set()
        for t in ths:
            t.join()
    final = CommTimers.aggregate(timers)
    assert final["pulls"] == sum(recorded)


# ------------------------------------------------- MetricsLogger (satellite)


def test_metrics_logger_log_is_thread_safe(tmp_path):
    """Concurrent log() from the bus receive thread and the train
    thread must never interleave two JSONL records into one torn line
    (the regression the new lock exists for)."""
    path = tmp_path / "m.jsonl"
    n_threads, n_lines = 6, 200
    with MetricsLogger(str(path), verbose=False) as m:
        def spam(tid):
            for i in range(n_lines):
                m.log(tid=tid, i=i, pad="x" * 256)
        ths = [threading.Thread(target=spam, args=(t,))
               for t in range(n_threads)]
        for t in ths:
            t.start()
        for t in ths:
            t.join()
    lines = path.read_text().splitlines()
    assert len(lines) == n_threads * n_lines
    for ln in lines:
        json.loads(ln)  # every line parses: no torn/interleaved writes


# --------------------------------------------------------------- tracer


def test_tracer_off_by_default_one_branch():
    assert trc.maybe_init(0) is None
    assert trc.TRACER is None  # the whole off-path cost is this check


def test_tracer_env_gated_records_and_dumps(tmp_path, monkeypatch):
    monkeypatch.setenv("MINIPS_TRACE", f"{tmp_path}:cap=64")
    tr = trc.maybe_init(3)
    assert tr is not None and tr.rank == 3 and tr.cap == 64
    t0 = time.monotonic()
    tr.instant("clock", "tick", {"clock": 1})
    tr.complete("pull", "pull_leg", t0, {"owner": 1, "rid": 7})
    tr.flow("s", trc.flow_id("pull", 3, 7), "pull")
    path = trc.dump_now()
    assert path == str(tmp_path / "trace-rank3.json")
    doc = json.load(open(path))
    evs = doc["traceEvents"]
    names = {e["name"] for e in evs}
    assert {"process_name", "tick", "pull_leg"} <= names
    leg = next(e for e in evs if e["name"] == "pull_leg")
    assert leg["ph"] == "X" and leg["dur"] >= 0 and leg["pid"] == 3
    flow = next(e for e in evs if e["ph"] == "s")
    assert flow["id"] == trc.flow_id("pull", 3, 7)


def test_tracer_ring_is_bounded(tmp_path):
    tr = trc.init(str(tmp_path), 0, cap=32)
    for i in range(500):
        tr.instant("clock", "tick", {"i": i})
    evs = tr.events_snapshot()
    assert len(evs) == 32
    # oldest dropped, newest kept: a dying run keeps its tail
    assert evs[-1][7]["i"] == 499 and evs[0][7]["i"] == 468


def test_tracer_reinit_same_rank_idempotent_divergent_raises(tmp_path):
    tr = trc.init(str(tmp_path), 1)
    assert trc.init(str(tmp_path), 1) is tr
    with pytest.raises(RuntimeError):
        trc.init(str(tmp_path), 2)


def test_flow_id_is_a_pure_function():
    assert trc.flow_id("pull", 0, 5) == trc.flow_id("pull", 0, 5)
    assert trc.flow_id("pull", 0, 5) != trc.flow_id("pull", 1, 5)
    assert trc.flow_id("pull", 0, 5) != trc.flow_id("push", 0, 5)
    # rids/seqs are PER-TABLE counters: the table name must be part of
    # the kind or two tables' rid 5 would merge into one arrow
    assert trc.flow_id("pull:a", 0, 5) != trc.flow_id("pull:b", 0, 5)


# ---------------------------------------------------------------- merge


def _mk_rank_doc(rank: int, events: list[dict]) -> dict:
    return {"traceEvents": events, "otherData": {"rank": rank}}


def _hb(rank: int, sender: int, ts_us: float, t_sent_s: float) -> dict:
    return {"ph": "i", "ts": ts_us, "cat": "hb", "name": "hb",
            "pid": rank, "tid": 1,
            "args": {"from": sender, "t_sent": t_sent_s}}


def test_merge_estimates_offsets_from_heartbeats(tmp_path):
    """Rank 1's clock runs 5000us AHEAD of rank 0's; symmetric one-way
    delay 300us. The NTP two-sample estimate recovers the offset."""
    off_us, delay = 5000.0, 300.0
    # rank 0 receives rank 1's beat: sent at t=1.0s on 1's clock
    # (= 1.0s - 5ms true), arrives 300us later on 0's clock
    r0 = [_hb(0, 1, (1.0 * 1e6 - off_us) + delay, 1.0)]
    # rank 1 receives rank 0's beat sent at t=2.0s on 0's clock
    r1 = [_hb(1, 0, (2.0 * 1e6 + off_us) + delay, 2.0)]
    traces = {0: _mk_rank_doc(0, r0), 1: _mk_rank_doc(1, r1)}
    offsets, unaligned = estimate_offsets_us(traces)
    assert unaligned == []
    assert abs(offsets[1] - off_us) < 1.0  # delays cancelled exactly
    assert offsets[0] == 0.0


def test_merge_links_cross_rank_flows_and_writes(tmp_path):
    fid = trc.flow_id("pull", 0, 9)
    r0 = [_hb(0, 1, 1000.0, 0.001),
          {"ph": "s", "ts": 500.0, "cat": "flow", "name": "pull",
           "pid": 0, "tid": 1, "id": fid}]
    r1 = [_hb(1, 0, 1000.0, 0.001),
          {"ph": "f", "bp": "e", "ts": 800.0, "cat": "flow",
           "name": "pull", "pid": 1, "tid": 1, "id": fid}]
    for rank, evs in ((0, r0), (1, r1)):
        with open(tmp_path / f"trace-rank{rank}.json", "w") as f:
            json.dump(_mk_rank_doc(rank, evs), f)
    doc, summary = merge_traces([str(tmp_path)])
    assert summary["flows_linked"] == 1
    assert summary["flow_pairs"] == {"0->1": 1}
    # the CLI: exit 0, writes the merged file, prints the summary
    rc = merge_main([str(tmp_path)])
    assert rc == 0
    merged = json.load(open(tmp_path / "merged_trace.json"))
    assert len(merged["traceEvents"]) == 4
    assert merged["otherData"]["flows_linked"] == 1


def test_merge_cli_fails_loudly_on_empty_dir(tmp_path):
    assert merge_main([str(tmp_path)]) == 1


# --------------------------------------------------------------- report


def test_report_attributes_blocked_time():
    evs = [
        {"ph": "X", "ts": 0.0, "dur": 1000_000.0, "cat": "clock",
         "name": "run", "pid": 0, "tid": 1},  # 1s wall anchor
        {"ph": "X", "ts": 100.0, "dur": 100_000.0, "cat": "pull",
         "name": "pull_wait", "pid": 0, "tid": 1,
         "args": {"owners": [1, 2]}},
        # the leg that finished LAST inside the wait span blames owner 2
        {"ph": "X", "ts": 100.0, "dur": 50_000.0, "cat": "pull",
         "name": "pull_leg", "pid": 0, "tid": 2,
         "args": {"owner": 1, "rid": 4}},
        {"ph": "X", "ts": 100.0, "dur": 99_000.0, "cat": "pull",
         "name": "pull_leg", "pid": 0, "tid": 2,
         "args": {"owner": 2, "rid": 5}},
        {"ph": "X", "ts": 300_000.0, "dur": 50_000.0, "cat": "clock",
         "name": "gate_wait", "pid": 0, "tid": 1,
         "args": {"clock": 3, "behind": [2]}},
        {"ph": "X", "ts": 500_000.0, "dur": 25_000.0, "cat": "pull",
         "name": "fence_wait", "pid": 0, "tid": 1, "args": {"n": 8}},
        # an --xla interleaved device event: NOT a rank, stays out
        {"ph": "X", "ts": 0.0, "dur": 9_000.0, "cat": "xla",
         "name": "fusion.1", "pid": 10_000, "tid": 1},
    ]
    attr = attribute({"traceEvents": evs})
    assert 10_000 not in attr
    r = attr[0]
    assert r["by"]["owner 2"] == 100_000.0  # last-finishing leg wins
    assert r["by"]["gate 2"] == 50_000.0
    assert r["by"]["fence"] == 25_000.0
    assert abs(r["blocked_frac"] - 0.175) < 0.01
    table = format_table(attr)
    assert "owner 2" in table and "17.5%" in table


# ------------------------------------------- in-process 2-rank drills


class _PairHarness:
    """Two trainers over loopback buses, threads as nodes."""

    def __init__(self, staleness=1, rows=64, dim=4):
        self.buses = mk_loopback_buses(2)
        self.tables = [ShardedTable("t", rows, dim, self.buses[i], i, 2,
                                    updater="sgd", lr=0.1,
                                    pull_timeout=20.0)
                       for i in range(2)]
        self.trainers = [ShardedPSTrainer({"t": self.tables[i]},
                                          self.buses[i], 2,
                                          staleness=staleness)
                         for i in range(2)]
        hs = [threading.Thread(target=b.handshake, args=(2,))
              for b in self.buses]
        for h in hs:
            h.start()
        for h in hs:
            h.join()

    def run(self, steps=5, finalize=True):
        errs = []

        def work(r):
            try:
                rng = np.random.default_rng(r)
                for _ in range(steps):
                    keys = rng.integers(0, self.tables[r].num_rows, 32)
                    rows = self.tables[r].pull(keys)
                    self.tables[r].push(keys, 0.01 * rows + 1.0)
                    self.trainers[r].tick()
                if finalize:
                    self.trainers[r].finalize(timeout=20.0)
            except Exception as e:  # noqa: BLE001
                errs.append(e)
        ths = [threading.Thread(target=work, args=(r,)) for r in (0, 1)]
        for t in ths:
            t.start()
        for t in ths:
            t.join()
        assert not errs, errs

    def close(self):
        for b in self.buses:
            b.close()


def test_wire_record_schema_full_layout():
    """THE done-line schema pin (satellite): every wire_record carries
    the full layout — including the new hist block — with None marking
    an OFF layer and {"count": 0}/zero-count dicts marking armed-but-
    idle, so sweep scrapers can tell the two apart."""
    h = _PairHarness()
    try:
        h.run(steps=4)
        rec = wire_record(h.trainers[0])
    finally:
        h.close()
    expected = {"bytes_pushed", "bytes_pulled", "frames_dropped",
                "wire_frames_lost", "wire_frames_malformed", "timing",
                "hist", "window", "heartbeat", "cache", "ef",
                "reliable", "chaos", "serve", "rebalance", "membership",
                "hedge", "slowness", "hier", "hybrid", "tenant",
                "freshness", "slo"}
    assert expected <= set(rec)
    # layers OFF in this run report None — not {} — and vice versa
    assert rec["cache"] is None
    assert rec["ef"] is None  # exact push wire: no residual store
    # freshness rides the serving plane: plane off -> None, not {}
    # (armed-idle pins live in test_traffic_obs.py)
    assert rec["freshness"] is None
    assert rec["slo"] is None  # MINIPS_SLO off: None, not zeros
    assert rec["hedge"] is None     # fail-slow plane off: both None
    assert rec["slowness"] is None
    assert rec["hier"] is None      # two-level push tree off: None
    assert rec["hybrid"] is None    # hybrid data plane off: None
    assert rec["reliable"] is None
    assert rec["chaos"] is None
    assert rec["rebalance"] is None
    assert rec["membership"] is None
    assert rec["heartbeat"] is None  # no monitor attached: off
    assert rec["tenant"] is None     # MINIPS_TENANT off: None, not {}
    # the hist block is ALWAYS a dict; populated quantities carry the
    # quantiles, idle ones carry {"count": 0}
    hist = rec["hist"]
    assert set(hist) == {"pull_latency_ms", "pull_blocked_ms",
                         "push_ack_ms", "serve_ms", "park_ms",
                         "fence_ms", "replica_serve_ms"}
    assert hist["pull_latency_ms"]["count"] > 0
    assert hist["replica_serve_ms"] == {"count": 0}  # plane off: idle
    assert hist["fence_ms"] == {"count": 0}  # no migrations: idle
    # the serving plane's off-vs-idle marker rides INSIDE the serve
    # block: None here (plane off; an armed-idle run reports zeros)
    assert rec["serve"]["replica"] is None
    assert {"p50_ms", "p95_ms", "p99_ms"} <= set(
        hist["pull_latency_ms"])
    assert hist["push_ack_ms"] == {"count": 0}  # async push off: idle
    # the timing block carries quantiles next to the means
    assert rec["timing"]["pull_latency_ms_p50"] is not None
    assert rec["timing"]["pull_latency_ms_mean"] is not None
    # the WINDOWED layer (obs/window.py) is always on by default: the
    # window block is a dict whose per-signal entries follow the same
    # off-vs-idle convention ({"count": 0} idle window), and the
    # pull-latency window saw this run's pulls
    win = rec["window"]
    assert win is not None and win["rolls"] >= 4
    assert win["hist"]["pull_latency"]["count"] > 0
    assert win["hist"]["fence"] == {"count": 0}
    # layers that are off never register their window signals
    assert "shed" not in win["rate_per_s"]
    assert "retransmits" not in win["rate_per_s"]


def test_app_done_line_splats_wire_record(capsys):
    """emit_multiproc_done must carry the FULL wire_record layout (it
    splats the record now instead of hand-copying fields — the
    hand-copied version had already silently dropped `timing` and
    `cache`)."""
    from minips_tpu.apps.common import emit_multiproc_done

    h = _PairHarness()
    try:
        h.run(steps=3)
        emit_multiproc_done(h.trainers[0], 0, time.monotonic(), [1.0],
                            1024, 0.5, extra_key=7)
    finally:
        h.close()
    line = [ln for ln in capsys.readouterr().out.splitlines()
            if ln.startswith("{")][-1]
    rec = json.loads(line)
    assert set(wire_record(h.trainers[0])) <= set(rec)
    assert rec["event"] == "done" and rec["extra_key"] == 7
    assert rec["hist"]["pull_latency_ms"]["count"] > 0


def test_bench_done_line_carries_wire_record_layout(capsys):
    """The standalone bench path builds the SAME record through its
    adapter — layout defined once in utils/metrics.wire_record."""
    from minips_tpu.apps import sharded_ps_bench

    rc = sharded_ps_bench.main(["--iters", "4", "--warmup", "1",
                                "--rows", "512", "--batch", "64"])
    assert rc == 0
    line = [ln for ln in capsys.readouterr().out.splitlines()
            if ln.startswith("{")][-1]
    rec = json.loads(line)
    for k in ("hist", "timing", "cache", "ef", "reliable", "chaos",
              "serve", "rebalance", "bytes_pushed", "bytes_pulled",
              "frames_dropped", "wire_frames_lost",
              "wire_frames_malformed", "trace_file"):
        assert k in rec, k
    assert rec["reliable"] is None and rec["trace_file"] is None
    assert rec["hist"]["pull_latency_ms"]["count"] > 0
    assert rec["timing"]["pull_latency_ms_p99"] is not None


def test_traced_run_produces_flows_and_spans(tmp_path, monkeypatch):
    """In-process acceptance slice: an SSP pair with MINIPS_TRACE armed
    leaves a dumped trace whose events cover the taxonomy's hot edges
    (pull legs, serves, waits, ticks) and whose pull flows LINK."""
    monkeypatch.setenv("MINIPS_TRACE", str(tmp_path))
    h = _PairHarness()
    try:
        h.run(steps=6)
    finally:
        h.close()
    # both in-process "ranks" share one tracer (rank 0): flows from
    # both sides land in one file and must still pair up by id
    doc = json.load(open(tmp_path / "trace-rank0.json"))
    evs = doc["traceEvents"]
    names = {e["name"] for e in evs}
    assert {"pull_leg", "pull_wait", "serve_pull", "tick",
            "push_apply"} <= names
    starts = {e["id"] for e in evs if e.get("ph") == "s"}
    ends = {e["id"] for e in evs if e.get("ph") == "f"}
    assert starts & ends, "no pull flow ever linked issue -> serve"


def test_bsp_traced_vs_untraced_bitwise_equal(tmp_path):
    """ACCEPTANCE: tracing never perturbs training — a deterministic
    BSP lockstep run produces BITWISE identical final weights with the
    tracer armed vs off (same harness as the chaos bitwise drill:
    disjoint cross-shard keys, per-link FIFO fixes the apply order)."""
    def run(trace_dir):
        trc.reset_for_tests()
        if trace_dir is not None:
            trc.init(str(trace_dir), 0)
        buses = mk_loopback_buses(2)

        class LockstepCons:  # shared lockstep clock vector (BSP: s=0)
            clocks = [0, 0]
            staleness = 0

            def __init__(self, rank):
                self.rank = rank

            @property
            def clock(self):
                return self.clocks[self.rank]

            def admit_pull(self, clk):
                return min(self.clocks) >= clk

            def serving_clock(self, requester):
                return min(self.clocks)

        tables = [ShardedTable("t", 64, 2, buses[i], i, 2,
                               updater="sgd", lr=0.5, pull_timeout=20.0)
                  for i in range(2)]
        LockstepCons.clocks = [0, 0]
        for i, t in enumerate(tables):
            t.bind_consistency(LockstepCons(i))
            t._w[...] = np.arange(32 * 2, dtype=np.float32
                                  ).reshape(32, 2) / 7.0
        keysets = [np.array([33, 40, 33, 47]), np.array([1, 8, 1, 15])]
        try:
            for _ in range(4):
                rows = [tables[r].pull(keysets[r]) for r in (0, 1)]
                for r in (0, 1):
                    tables[r].push(keysets[r], 0.1 * rows[r] + 1.0)
                for r in (0, 1):
                    tables[r].pull(keysets[r])
                LockstepCons.clocks[0] += 1
                LockstepCons.clocks[1] += 1
            return [t._w.copy() for t in tables]
        finally:
            for b in buses:
                b.close()
            trc.reset_for_tests()

    w_off = run(None)
    w_on = run(tmp_path / "tr")
    assert (tmp_path / "tr").exists()  # the traced run really traced
    for off, on in zip(w_off, w_on):
        np.testing.assert_array_equal(off, on)  # bitwise, not allclose


# ----------------------------------------------- slow tier: e2e drills

_BENCH = [sys.executable, "-m", "minips_tpu.apps.sharded_ps_bench",
          "--iters", "14", "--warmup", "3", "--batch", "512",
          "--rows", "8192", "--staleness", "1"]


def _merge_cli(trace_dir: str) -> dict:
    """Run the REAL merge CLI (the TRACE-MERGE gate's contract is its
    exit code) and return its summary line."""
    proc = subprocess.run(
        [sys.executable, "-m", "minips_tpu.obs.merge", trace_dir],
        capture_output=True, text=True, timeout=120.0)
    assert proc.returncode == 0, proc.stderr
    return json.loads(proc.stdout.splitlines()[-1])


@pytest.mark.slow
def test_e2e_3proc_ssp_trace_merges_with_flow_per_owner(tmp_path):
    """ACCEPTANCE: a 3-proc SSP run with tracing armed leaves per-rank
    traces the merge CLI combines into one valid Chrome trace holding
    >= 1 client-pull→owner-serve flow pair PER REMOTE OWNER, and the
    done lines carry p50/p95/p99 pull-latency histograms."""
    tdir = str(tmp_path / "traces")
    res = launch.run_local_job(3, _BENCH + ["--trace", tdir],
                               base_port=None, timeout=240.0)
    for r in res:
        assert r["trace_file"] == os.path.join(
            tdir, f"trace-rank{r['rank']}.json")
        assert os.path.exists(r["trace_file"])
        h = r["hist"]["pull_latency_ms"]
        assert h["count"] > 0 and h["p50_ms"] is not None \
            and h["p95_ms"] is not None and h["p99_ms"] is not None
        assert r["timing"]["pull_latency_ms_p99"] is not None
    summary = _merge_cli(tdir)
    assert summary["flows_linked"] >= 6
    # one flow pair per (client, remote owner) direction: 3 ranks -> 6
    for a in range(3):
        for b in range(3):
            if a != b:
                assert summary["flow_pairs"].get(f"{a}->{b}", 0) >= 1, \
                    (a, b, summary["flow_pairs"])
    # the merged trace is valid Chrome-trace JSON the report can read
    merged = json.load(open(os.path.join(tdir, "merged_trace.json")))
    attr = attribute(merged)
    assert set(attr) == {0, 1, 2}
    assert all(r["blocked_us"] >= 0 for r in attr.values())


@pytest.mark.slow
def test_e2e_3proc_trace_chaos_shows_retransmit_spans(tmp_path):
    """ACCEPTANCE: under seeded MINIPS_CHAOS drop with the reliable
    layer on, the merged trace carries the injected drops AND the
    retransmit spans that recovered them."""
    tdir = str(tmp_path / "traces")
    res = launch.run_local_job(
        3, _BENCH + ["--trace", tdir, "--pull-timeout", "30"],
        base_port=None,
        env_extra={"MINIPS_CHAOS": "4242:drop=0.02",
                   "MINIPS_RELIABLE": "1"},
        timeout=240.0)
    assert all(r["wire_frames_lost"] == 0 for r in res)
    assert sum(r["chaos"]["dropped"] for r in res) > 0
    assert sum(r["reliable"]["recovered"] for r in res) > 0
    merged = json.load(open(_merge_cli(tdir)["merged"]))
    names = [e["name"] for e in merged["traceEvents"]]
    assert "drop" in names, "chaos injections missing from the trace"
    rts = [e for e in merged["traceEvents"]
           if e["name"] == "retransmit" and e["ph"] == "X"]
    assert rts, "no retransmit spans despite recovered drops"
    assert all(e["dur"] > 0 for e in rts)


@pytest.mark.slow
def test_e2e_3proc_trace_rebalance_shows_fence_spans(tmp_path):
    """ACCEPTANCE: with MINIPS_REBALANCE armed on unpermuted zipf the
    merged trace carries the migration's adopt/ship/fence events —
    fence spans with duration, adoption spans on every rank."""
    tdir = str(tmp_path / "traces")
    res = launch.run_local_job(
        3, _BENCH + ["--trace", tdir, "--key-dist", "zipf",
                     "--no-zipf-permute-hot", "--iters", "30"],
        base_port=None,
        env_extra={"MINIPS_REBALANCE":
                   "interval=0.25,threshold=1.2,max_blocks=16,"
                   "block=16,topk=64"},
        timeout=240.0)
    assert sum(r["rebalance"]["blocks_in"] for r in res) >= 1, \
        "no migration happened; the drill is vacuous"
    merged = json.load(open(_merge_cli(tdir)["merged"]))
    names = [e["name"] for e in merged["traceEvents"]]
    assert "rb_adopt" in names and "rb_ship" in names
    fences = [e for e in merged["traceEvents"]
              if e["name"] == "rb_fence" and e["ph"] == "X"]
    assert fences, "no fence spans despite completed migrations"
    assert all(e["dur"] >= 0 for e in fences)
