"""Sharded multi-process PS throughput curve (VERDICT r2 #2).

Measures train/sharded_ps.py — the key-range-sharded multi-process server —
via apps/sharded_ps_bench.py workers: rows/sec and wire-bytes/sec of the
pull→push cycle per process, with model math stripped out so the number
isolates routing + serialization + bus + server-side updater (the
reference's Mailbox/ServerThread hot path, SURVEY.md §3.3 hot spots b+c).

The sweep:
- world size 1 (standalone, zero wire: the pure server-apply ceiling)
  then 2→4 real processes over loopback;
- zmq vs the native C++ TCP mailbox at world size 3;
- sparse key-slice path vs dense contiguous-range path at world size 3.

Everything here is HOST-CPU loopback — the sharded PS is the control-plane
topology (real pods put one process per node); these are deliberately NOT
chip rates and never feed vs_baseline. Emits ONE JSON line.

Usage: python bench_sharded_ps.py [--iters 60] [--quick]
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys

def _worker_argv(path: str, iters: int, warmup: int,
                 compute: str = "none",
                 hidden: int | None = None,
                 push_comm: str = "float32",
                 pull_wire: str = "f32",
                 overlap: bool = False,
                 overlap_legs: str = "both",
                 key_dist: str = "uniform",
                 staleness: float | None = None,
                 cache_bytes: int = 0,
                 pull_dedup: bool = True,
                 push_dedup: bool = True,
                 rows: int | None = None,
                 updater: str | None = None,
                 pull_timeout: float | None = None,
                 zipf_permute_hot: bool = True,
                 trace: str | None = None) -> list[str]:
    argv = [sys.executable, "-m", "minips_tpu.apps.sharded_ps_bench",
            "--path", path, "--iters", str(iters), "--warmup", str(warmup)]
    if trace:
        argv += ["--trace", trace]
    if compute != "none":
        argv += ["--compute", compute]
    if hidden is not None:
        argv += ["--hidden", str(hidden)]
    if push_comm != "float32":
        argv += ["--push-comm", push_comm]
    if pull_wire != "f32":
        argv += ["--pull-wire", pull_wire]
    if overlap:
        argv += ["--overlap"]
        if overlap_legs != "both":
            argv += ["--overlap-legs", overlap_legs]
    if key_dist != "uniform":
        argv += ["--key-dist", key_dist]
    if not zipf_permute_hot:
        argv += ["--no-zipf-permute-hot"]
    if staleness is not None:
        argv += ["--staleness", str(staleness)]
    if cache_bytes:
        argv += ["--cache-bytes", str(cache_bytes)]
    if not pull_dedup:
        argv += ["--no-pull-dedup"]
    if not push_dedup:
        argv += ["--no-push-dedup"]
    if rows is not None:
        argv += ["--rows", str(rows)]
    if updater is not None:
        argv += ["--updater", updater]
    if pull_timeout is not None:
        argv += ["--pull-timeout", str(pull_timeout)]
    return argv


def _run(n: int, path: str, iters: int, warmup: int, bus: str,
         compute: str = "none", force_cpu: bool = False,
         hidden: int | None = None, push_comm: str = "float32",
         pull_wire: str = "f32", overlap: bool = False,
         overlap_legs: str = "both", key_dist: str = "uniform",
         staleness: float | None = None, cache_bytes: int = 0,
         pull_dedup: bool = True, push_dedup: bool = True,
         rows: int | None = None,
         updater: str | None = None,
         chaos: str | None = None, reliable: bool = False,
         pull_timeout: float | None = None,
         zipf_permute_hot: bool = True, rebalance: str | None = None,
         trace: str | None = None, wire_fmt: str | None = None,
         obs: str | None = None, flight: str | None = None,
         may_fail: bool = False, timeout: float = 300.0) -> dict:
    """One sweep point → {rows_per_sec_per_process, aggregate, wire...}.

    ``compute="jit"`` adds a real jitted model-grad step between pull and
    push on every worker — the north-star topology (accelerator workers
    against a sharded host PS) instead of the bare control plane. One
    accelerator belongs to one process, so the job states each rank's
    device (``launch.check_device_claims``): rank 0 keeps the default
    backend unless ``force_cpu``, every peer is pinned to the CPU; each
    rank echoes the backend it actually ran on (``worker_compute``).
    ``hidden`` sizes that step's MLP."""
    argv = _worker_argv(path, iters, warmup, compute, hidden,
                        push_comm, pull_wire, overlap, overlap_legs,
                        key_dist, staleness, cache_bytes, pull_dedup,
                        push_dedup, rows, updater, pull_timeout,
                        zipf_permute_hot, trace)
    # ALWAYS pinned, even for the zmq arms: an armed MINIPS_BUS=shm in
    # the invoking shell must not silently move the zmq baseline arms
    # onto the shm backend (TRANSPORT-WIN would then compare shm vs shm)
    env_extra = {"MINIPS_BUS": bus}
    env_per_rank = None
    if force_cpu:
        env_extra["MINIPS_FORCE_CPU"] = "1"
    elif compute == "jit":
        env_per_rank = {r: {"JAX_PLATFORMS": "cpu"} for r in range(1, n)}
    else:  # a control-plane arm: no rank has device work
        env_extra["JAX_PLATFORMS"] = "cpu"
    # chaos/reliable arms configure via env (launcher-inherited, no
    # per-app flag plumbing); explicit empty strings keep an armed
    # environment from leaking into the clean arms — MINIPS_TRACE too:
    # the traced arm uses the worker's --trace flag, and an armed
    # environment must not silently trace (and tax) every other arm
    env_extra["MINIPS_CHAOS"] = chaos or ""
    env_extra["MINIPS_RELIABLE"] = "1" if reliable else ""
    env_extra["MINIPS_REBALANCE"] = rebalance or ""
    env_extra["MINIPS_TRACE"] = ""
    # elastic membership + kill/liveness knobs pinned off for the same
    # reason: an armed environment must not leak into non-elastic arms
    env_extra["MINIPS_ELASTIC"] = ""
    env_extra["MINIPS_CHAOS_KILL"] = ""
    env_extra["MINIPS_HEARTBEAT"] = ""
    # planned redistribution schedules migration state rounds — an
    # armed MINIPS_RESHARD must not silently re-lane (or refuse, with
    # no rebalancer armed) the non-reshard arms
    env_extra["MINIPS_RESHARD"] = ""
    # multi-tenant tables ride their own sweep; an armed
    # MINIPS_TENANT must not stamp (and re-bucket) the other arms
    env_extra["MINIPS_TENANT"] = ""
    # SLO burn accounting + the open-loop traffic driver ride the
    # million_user sweep; an armed MINIPS_SLO would flex replica
    # budgets (and pressure the autoscaler) under every other arm
    env_extra["MINIPS_SLO"] = ""
    env_extra["MINIPS_TRAFFIC"] = ""
    # the in-mesh collective plane rides its own sweep via --plane; an
    # armed MINIPS_MESH must not reroute (or refuse) the wire arms
    env_extra["MINIPS_MESH"] = ""
    # the hierarchical push tree + its hybrid (agg=mesh) backend ride
    # their own sweeps; an armed MINIPS_HIER must not silently re-lane
    # every wire arm's pushes through a tree (each arm's rate would
    # then measure the tree, not the lever under test)
    env_extra["MINIPS_HIER"] = ""
    env_extra["MINIPS_HIER_MESH_COMM"] = ""
    env_extra["MINIPS_HIER_MESH_DEVS"] = ""
    # head-codec arm config (the transport sweep): explicit empty keeps
    # an armed environment from leaking a format into the other arms
    env_extra["MINIPS_WIRE_FMT"] = wire_fmt or ""
    # push-wire tier rides the --push-comm flag; the env spelling is
    # pinned EMPTY so an armed MINIPS_PUSH_COMM can't silently move a
    # baseline arm onto the compressed wire (the table's env default
    # only fires when the flag is absent — which is every f32 arm)
    env_extra["MINIPS_PUSH_COMM"] = ""
    # windowed-metrics + flight-recorder layers: empty = their DEFAULT
    # (both always-on — that is the point of this layer), "0" = off
    # (only the obs_tax_3proc off arm passes it: the honesty A/B)
    env_extra["MINIPS_OBS"] = obs or ""
    env_extra["MINIPS_FLIGHT"] = flight or ""
    if n == 1:  # standalone zero-wire baseline (no launcher, no bus)
        proc = subprocess.run(argv, capture_output=True, text=True,
                              timeout=timeout,
                              env={**os.environ, **env_extra})
        if proc.returncode != 0:
            raise RuntimeError(f"standalone worker failed: {proc.stderr}")
        res = [json.loads([ln for ln in proc.stdout.splitlines()
                           if ln.startswith("{")][-1])]
    else:
        from minips_tpu import launch

        try:
            res = launch.run_local_job(
                n, argv, base_port=None,  # OS-assigned free block
                env_extra=env_extra or None, env_per_rank=env_per_rank,
                timeout=timeout)
        except Exception as e:  # noqa: BLE001 - may_fail arms record it
            if not may_fail:
                raise
            # the chaos sweep's retransmit-off arms are EXPECTED to die
            # (that outcome is the measurement): record the death WITHOUT
            # a rows_per_sec_per_process key — the arm's outcome is
            # bimodal by design, so it must never enter the run-to-run
            # REGRESSED/MISSING throughput gate in either direction
            return {"completed": False, "error": str(e)[:300]}
    per = [r["rows_per_sec"] for r in res]
    wire = [r["wire_push_bytes_per_sec"] + r["wire_pull_bytes_per_sec"]
            for r in res]
    out = {
        "rows_per_sec_per_process": round(statistics.mean(per), 1),
        "completed": True,
        "aggregate_rows_per_sec": round(sum(per), 1),
        "wire_bytes_per_sec_per_process": round(statistics.mean(wire), 1),
        # 1 decimal: the sweep-point resolution the artifact history uses
        # (26.7 f32 both legs → 20.0 one int8 leg → 13.3 both)
        "wire_bytes_per_row_moved": round(statistics.mean(
            [r["wire_bytes_per_row_moved"] for r in res]), 1),
        # the push leg alone (WIRE-BYTES gates it: the compressed push
        # tiers move push bytes only, so the pull leg must not dilute
        # the comparison) — same rows-moved denominator as above
        "wire_push_bytes_per_row_moved": round(statistics.mean(
            [r["wire_push_bytes_per_sec"] / max(r["rows_per_sec"], 1e-9)
             for r in res]), 3),
    }
    efs = [r.get("ef") for r in res]
    if any(e is not None for e in efs):
        out["ef_resident_rows"] = sum((e or {}).get("resident_rows", 0)
                                      for e in efs)
        out["ef_flushed_rows"] = sum(
            (e or {}).get("flushed_age", 0)
            + (e or {}).get("flushed_fence", 0)
            + (e or {}).get("flushed_overflow", 0) for e in efs)
    fracs = [r["timing"].get("pull_overlap_fraction")
             for r in res if r.get("timing")]
    fracs = [f for f in fracs if f is not None]
    if fracs:
        out["pull_overlap_fraction"] = round(statistics.mean(fracs), 4)
    if compute != "none":
        out["worker_compute"] = sorted({r.get("compute", "?")
                                        for r in res})
    # row-flow + cache observables (the dedup/cache sweep's evidence):
    # wire-row fraction from the per-rank timers; hit rate from the
    # caches (None — distinct from 0.0 — when the arm runs cache-off)
    reqs = sum(r["timing"].get("pull_rows_requested", 0) for r in res)
    wires = sum(r["timing"].get("pull_rows_wire", 0) for r in res)
    if reqs:
        out["pull_rows_wire_frac"] = round(wires / reqs, 4)
    caches = [r.get("cache") for r in res]
    if any(c is not None for c in caches):
        hits = sum(c["hits"] for c in caches if c)
        looks = sum(c["lookups"] for c in caches if c)
        out["cache_hit_rate"] = (round(hits / looks, 4) if looks
                                 else 0.0)
    # the workers echo their wire formats — a silent flag-plumbing
    # regression must not publish a float32 number labeled int8 (nor a
    # synchronous number labeled overlapped)
    echoed = {r.get("push_comm", "float32") for r in res}
    assert echoed == {push_comm}, (push_comm, echoed)
    echoed_pw = {r.get("pull_wire", "f32") for r in res}
    assert echoed_pw == {pull_wire}, (pull_wire, echoed_pw)
    echoed_ov = {bool(r.get("overlap")) for r in res}
    assert echoed_ov == {overlap}, (overlap, echoed_ov)
    echoed_legs = {r.get("overlap_legs") for r in res}
    assert echoed_legs == {overlap_legs if overlap else None}, (
        overlap_legs, echoed_legs)
    echoed_kd = {r.get("key_dist", "uniform") for r in res}
    assert echoed_kd == {key_dist}, (key_dist, echoed_kd)
    echoed_cb = {r.get("cache_bytes", 0) for r in res}
    assert echoed_cb == {cache_bytes}, (cache_bytes, echoed_cb)
    echoed_dd = {r.get("pull_dedup", True) for r in res}
    assert echoed_dd == {pull_dedup}, (pull_dedup, echoed_dd)
    echoed_pd = {r.get("push_dedup", True) for r in res}
    assert echoed_pd == {push_dedup}, (push_dedup, echoed_pd)
    echoed_ch = {r.get("chaos_spec") for r in res}
    assert echoed_ch == {chaos or None}, (chaos, echoed_ch)
    echoed_rl = {bool(r.get("reliable_on")) for r in res}
    assert echoed_rl == {bool(reliable)}, (reliable, echoed_rl)
    echoed_rb = {r.get("rebalance_spec") for r in res}
    assert echoed_rb == {rebalance or None}, (rebalance, echoed_rb)
    if n > 1:  # wire-format echo (standalone runs have no bus)
        echoed_wf = {r.get("wire_fmt") for r in res}
        assert echoed_wf == {wire_fmt or "bin"}, (wire_fmt, echoed_wf)
    if trace:  # every rank of a traced arm must have dumped its file
        assert all(r.get("trace_file") for r in res), \
            [r.get("trace_file") for r in res]
    if key_dist == "zipf":
        echoed_ph = {r.get("zipf_permute_hot") for r in res}
        assert echoed_ph == {zipf_permute_hot}, (zipf_permute_hot,
                                                 echoed_ph)
    # per-owner serve load: max/mean across ranks is the partition-
    # imbalance observable (1.0 = balanced) — the rebalance sweep's
    # REBAL-SKEW tripwire compares it between arms
    srv = [r.get("serve") for r in res]
    if all(s is not None for s in srv):
        rows_served = [s["pull_rows"] + s["push_rows"] for s in srv]
        mean_served = sum(rows_served) / len(rows_served)
        out["serve_rows_per_rank"] = rows_served
        if mean_served > 0:
            out["serve_load_imbalance"] = round(
                max(rows_served) / mean_served, 4)
    rbs = [r.get("rebalance") for r in res if r.get("rebalance")]
    if rbs:
        out["migrations"] = sum(r["blocks_in"] for r in rbs)
        out["routing_epoch"] = max(r["epoch"] for r in rbs)
    # wire-health roll-up for the resilience sweep: unrecovered loss must
    # read 0 on every completed chaos arm, and the recovery counters are
    # the evidence the layer (not luck) carried the run
    lost = sum(r.get("wire_frames_lost", 0) for r in res)
    out["wire_frames_lost"] = lost
    rels = [r.get("reliable") for r in res if r.get("reliable")]
    if rels:
        out["retransmits_got"] = sum(r["retransmits_got"] for r in rels)
        out["nacks_sent"] = sum(r["nacks_sent"] for r in rels)
        out["frames_gave_up"] = sum(r["gave_up"] for r in rels)
    chs = [r.get("chaos") for r in res if r.get("chaos")]
    if chs:
        out["chaos_dropped"] = sum(c["dropped"] for c in chs)
    if staleness is not None:
        echoed_s = {r.get("staleness") for r in res}
        assert echoed_s == {int(staleness)}, (staleness, echoed_s)
    return out


def fail_slow_arms(quick: bool = False) -> dict:
    import glob as _glob
    import tempfile

    from minips_tpu import launch as _launch

    f_iters = 30 if quick else 40
    fbase = [sys.executable, "-m",
             "minips_tpu.apps.sharded_ps_example",
             "--model", "sparse", "--mode", "ssp",
             "--staleness", "2", "--iters", str(f_iters),
             "--batch", "64",
             # the read storm aims at rank 1's hot range from step
             # 2 THROUGH the last step so the windowed (last-K-
             # rolls) p99 measures warmed steady-state reads, past
             # the cold-start replica promotion window
             "--storm-from", "2", "--storm-until", str(f_iters),
             "--storm-pulls", "6", "--storm-keys", "64"]
    env0 = {"MINIPS_FORCE_CPU": "1", "JAX_PLATFORMS": "cpu",
            "MINIPS_RESHARD": "",
            "MINIPS_RELIABLE": "", "MINIPS_REBALANCE": "",
            "MINIPS_TRACE": "", "MINIPS_SERVE": "",
            "MINIPS_BUS": "", "MINIPS_WIRE_FMT": "",
            "MINIPS_CHAOS_KILL": "", "MINIPS_PUSH_COMM": "",
            "MINIPS_MESH": "", "MINIPS_AUTOSCALE": "",
            "MINIPS_TENANT": "",
            "MINIPS_SLO": "", "MINIPS_TRAFFIC": "",
            "MINIPS_ELASTIC": "", "MINIPS_SLOW": "",
            "MINIPS_HEDGE": "", "MINIPS_OBS": "",
            "MINIPS_FLIGHT": "", "MINIPS_HEARTBEAT": "",
            # the injection: every frame FROM rank 1 arrives 40ms
            # late at both peers (replies, acks, clock gossip —
            # the whole outbound plane of a sick NIC), jittered
            # ~8ms on the 1->2 link so detection sees variance
            "MINIPS_CHAOS": "11:slow#1>0=40,slow#1>2=40~8"}
    serve = ("replicas=1,hot=200,topk=200,interval=0.05,"
             "min_heat=1")
    grid: dict = {"iters": f_iters, "sick_rank": 1,
                  "reader_rank": 0}

    def arm(name: str, extra_env: dict, flight: str = "") -> dict:
        try:
            res = _launch.run_local_job(
                3, list(fbase), base_port=None,
                env_extra={**env0, **extra_env}, timeout=240.0)
            win = [(((d.get("window") or {}).get("hist") or {})
                    .get("pull_latency") or {}) for d in res]
            sums = {d.get("param_sum") for d in res}
            hedges = [d.get("hedge") or {} for d in res]
            slw = [d.get("slowness") or {} for d in res]
            out = {
                "completed": all(d.get("event") == "done"
                                 for d in res),
                "steps_per_sec_slow": round(
                    f_iters / max(max(d["wall_s"] for d in res),
                                  1e-9), 2),
                "clock_min": min(d.get("clock", 0) for d in res),
                # the SLOW-HEDGE observable: the designated
                # reader's WARMED windowed read p99 (rank 0 — not
                # a holder, so its slow legs must hedge over the
                # wire; cumulative p99 would charge the arm for
                # the pre-promotion cold start)
                "reader_p99_ms": win[0].get("p99_ms"),
                "p99_ms_by_rank": [w.get("p99_ms") for w in win],
                "hedges_fired": sum(h.get("fired", 0)
                                    for h in hedges),
                "hedges_won": sum(h.get("won", 0)
                                  for h in hedges),
                "slow_suspects_raised": sum(
                    s.get("suspects_raised", 0) for s in slw),
                "slow_verdicts": sum(
                    (d.get("membership") or {}).get(
                        "slow_verdicts", 0) for d in res),
                "sick_blocks_out": (res[1].get("rebalance")
                                    or {}).get("blocks_out", 0),
                "slowed": sum((d.get("chaos") or {}).get(
                    "slowed", 0) for d in res),
                "wire_frames_lost": sum(
                    d.get("wire_frames_lost", 0) for d in res),
                "finals_agree": len(sums) == 1,
            }
            if flight:
                files = sorted(_glob.glob(os.path.join(
                    flight, "flight-rank*.json")))
                kinds: set = set()
                for fp in files:
                    with open(fp) as fh:
                        doc = json.load(fh)
                    kinds |= {e.get("kind")
                              for e in doc.get("events", ())}
                want = {"slow_suspect", "slow_verdict",
                        "hedge_fired", "demote"}
                out["flight_dumps"] = len(files)
                out["flight_events"] = sorted(kinds & want)
                out["flight_events_ok"] = want <= kinds
            return out
        except Exception as e:  # noqa: BLE001 - completion-gated
            return {"completed": False, "error": str(e)[:300]}

    grid["unmitigated"] = arm("unmitigated", {})
    grid["hedged"] = arm("hedged", {
        "MINIPS_SERVE": serve, "MINIPS_HEDGE": "delay_ms=15"})
    with tempfile.TemporaryDirectory() as fdir:
        grid["demote"] = arm("demote", {
            "MINIPS_SERVE": serve, "MINIPS_HEDGE": "delay_ms=15",
            "MINIPS_ELASTIC": "1",
            "MINIPS_SLOW": ("factor=3,windows=2,window=5,"
                            "min_ms=15,min_samples=2,demote=4"),
            "MINIPS_REBALANCE": ("block=2048,threshold=3,"
                                 "interval=0.3,min_heat=1"),
            "MINIPS_HEARTBEAT": "interval=0.1,timeout=2.0",
            "MINIPS_FLIGHT": fdir}, flight=fdir)
    # SLOW-IDLE: hedge-armed vs off on a clean wire, bitwise
    try:
        proc = subprocess.run(
            [sys.executable, "-m",
             "minips_tpu.apps.sharded_ps_bench",
             "--fail-slow-idle-drill"],
            capture_output=True, text=True, timeout=300.0,
            cwd=os.path.dirname(os.path.abspath(__file__)),
            env={**os.environ, "MINIPS_FORCE_CPU": "1",
                 "JAX_PLATFORMS": "cpu", "MINIPS_MESH": "",
                 "MINIPS_CHAOS": "", "MINIPS_HEDGE": "",
                 "MINIPS_SLOW": ""})
        res = json.loads([ln for ln in proc.stdout.splitlines()
                          if ln.startswith("{")][-1])
        grid["idle"] = {"equal": bool(res.get("bitwise_equal")),
                        "rows_checked":
                            int(res.get("rows_checked", 0))}
        if res.get("error"):
            grid["idle"]["error"] = res["error"]
    except Exception as e:  # noqa: BLE001 - the gate reads this
        grid["idle"] = {"equal": False, "rows_checked": 0,
                        "error": str(e)[:300]}
    return grid


def tenant_arms(quick: bool = False) -> dict:
    """THE MULTI-TENANT SWEEP: one 3-proc job runs a training tenant
    (``trn`` — every rank's sparse pull+push loop at a fixed step
    pace; pace-KEPT rows/sec is the protected number) next to a
    storming zipf inference tenant (``inf`` — per-rank reader threads
    free-running ``pull_serving`` into admission). Four arms: ``solo``
    (trn alone — the protected baseline), ``isolated`` (per-tenant
    buckets: trn admission off, inf throttled into its own budget),
    ``shared`` (``shared=1`` — ONE fleet bucket, the coupling the
    per-tenant split removes), and ``idle`` (the --tenant-idle-drill
    bitwise stamp). TENANT-ISO wants isolated trn within 10% of solo
    with inf shedding into its own budget and trn's attributed
    counters ZERO (and the shared arm's coupling engaged — the
    contrast must be real); TENANT-IDLE wants the idle stamp green."""
    from minips_tpu import launch as _launch

    t_iters = 15 if quick else 40
    tbase = [sys.executable, "-m", "minips_tpu.apps.sharded_ps_bench",
             "--tenant-bench", "--path", "sparse",
             "--iters", str(t_iters),
             "--warmup", str(max(2, t_iters // 6)),
             "--batch", "128", "--rows", "4096",
             # the storm must be heavy in REQUESTS, not in raw CPU:
             # these readers share each rank's interpreter with the
             # trainer, so a zero-think closed loop measures GIL
             # contention (which no admission split can remove), not
             # tenancy — 25ms think keeps the reader threads asleep
             # between attempts while the attempt rate still over-
             # drives the inf bucket into visible shedding
             "--storm-batch", "8", "--storm-think-ms", "25",
             # pace-kept SLO: each trn step sleeps to a 60ms deadline
             # (roughly 4x the unloaded pull+push+tick time), so
             # trn_rows_per_sec compares PACE-KEEPING across arms —
             # storm-tax jitter lands in the slack, only real stalls
             # (shared-bucket denials riding retry_ms) slip deadlines
             "--trn-step-ms", "60",
             "--staleness", "1", "--updater", "sgd",
             "--key-dist", "zipf", "--no-zipf-permute-hot",
             "--pull-timeout", "30"]
    serve = ("replicas=1,hot=16,topk=64,interval=0.05,min_heat=1,"
             "rate=40,burst=8")
    env0 = {"MINIPS_FORCE_CPU": "1", "JAX_PLATFORMS": "cpu",
            "MINIPS_CHAOS": "", "MINIPS_RELIABLE": "1",
            "MINIPS_REBALANCE": "", "MINIPS_TRACE": "",
            "MINIPS_SERVE": "", "MINIPS_BUS": "",
            "MINIPS_WIRE_FMT": "", "MINIPS_ELASTIC": "",
            "MINIPS_CHAOS_KILL": "", "MINIPS_HEARTBEAT": "",
            "MINIPS_PUSH_COMM": "", "MINIPS_MESH": "",
            "MINIPS_AUTOSCALE": "", "MINIPS_RESHARD": "",
            "MINIPS_SLOW": "", "MINIPS_HEDGE": "",
            "MINIPS_TENANT": "",
            "MINIPS_SLO": "", "MINIPS_TRAFFIC": ""}
    # per-tenant buckets: trn's admission OFF (its SLO is throughput),
    # inf throttled into its own budget; inf reads at its OWN s=2
    # against the job's staleness=1
    iso_spec = "trn:rate=0;inf:rate=40,burst=8,s=2"
    grid: dict = {"iters": t_iters, "serve_spec": serve,
                  "isolated_spec": iso_spec}

    def arm(tenant_spec: str, storm: int) -> dict:
        argv = list(tbase) + ["--storm", str(storm),
                              "--serve", serve,
                              "--tenant", tenant_spec]
        try:
            res = _launch.run_local_job(3, argv, base_port=None,
                                        env_extra=env0, timeout=240.0)
        except Exception as e:  # noqa: BLE001 - completion-gated
            return {"completed": False, "error": str(e)[:300]}
        echoed = {r.get("tenant_spec") for r in res}
        assert echoed == {tenant_spec}, (tenant_spec, echoed)
        tb = [r.get("tenant") or {} for r in res]

        def tcnt(tname: str, key: str) -> int:
            return sum(((b.get("tenants") or {}).get(tname) or {})
                       .get(key, 0) for b in tb)

        rep = [(r["serve"] or {}).get("replica") for r in res]
        return {
            "completed": all(r.get("event") == "done" for r in res),
            # the protected number: the training tenant's fleet rate
            "trn_rows_per_sec": round(
                sum(r["trn_rows_per_sec"] for r in res), 1),
            "read_rows_per_sec": round(
                sum(r["read_rows_per_sec"] for r in res), 1),
            "shared": max(b.get("shared", 0) for b in tb),
            # per-tenant deny attribution — THE isolation evidence
            "trn_denied": (tcnt("trn", "shed")
                           + tcnt("trn", "throttle")),
            "inf_denied": (tcnt("inf", "shed")
                           + tcnt("inf", "throttle")),
            # staleness-bound evidence: zero on BOTH ledgers (the
            # tenant-attributed counter and the plane's own)
            "stale_reads": (tcnt("trn", "stale_reads")
                            + tcnt("inf", "stale_reads")
                            + sum((x or {}).get("stale_reads") or 0
                                  for x in rep)),
            "wire_frames_lost": sum(r.get("wire_frames_lost", 0)
                                    for r in res),
            "frames_dropped": sum(r.get("frames_dropped", 0)
                                  for r in res),
        }

    grid["solo"] = arm(iso_spec, 0)
    grid["isolated"] = arm(iso_spec, 2)
    # ONE fleet bucket (cfg rate=40 shared by both tenants): the
    # combined load drains tokens the quiet tenant needed — the
    # coupling the per-tenant split exists to remove
    grid["shared"] = arm("trn;inf:s=2;shared=1", 2)
    # TENANT-IDLE: bare default tenant vs off, bitwise + zero counters
    try:
        proc = subprocess.run(
            [sys.executable, "-m",
             "minips_tpu.apps.sharded_ps_bench",
             "--tenant-idle-drill"],
            capture_output=True, text=True, timeout=300.0,
            cwd=os.path.dirname(os.path.abspath(__file__)),
            env={**os.environ, "MINIPS_FORCE_CPU": "1",
                 "JAX_PLATFORMS": "cpu", "MINIPS_MESH": "",
                 "MINIPS_CHAOS": "", "MINIPS_TENANT": "",
            "MINIPS_SLO": "", "MINIPS_TRAFFIC": ""})
        res = json.loads([ln for ln in proc.stdout.splitlines()
                          if ln.startswith("{")][-1])
        grid["idle"] = {"equal": bool(res.get("bitwise_equal")),
                        "rows_checked":
                            int(res.get("rows_checked", 0)),
                        "tenant_tids": res.get("tenant_tids"),
                        "tenant_counters": res.get("tenant_counters")}
        if res.get("error"):
            grid["idle"]["error"] = res["error"]
    except Exception as e:  # noqa: BLE001 - the gate reads this
        grid["idle"] = {"equal": False, "rows_checked": 0,
                        "error": str(e)[:300]}
    return grid


def traffic_arms(quick: bool = False) -> dict:
    """THE MILLION-USER SWEEP (million_user_3proc): the open-loop
    traffic driver (apps/traffic_driver.py) replays seeded zipf user
    streams against the ``inf`` table's ``pull_serving`` on a FIXED
    arrival schedule — latency measured from scheduled arrival, so a
    fleet that falls behind shows the queueing it caused instead of
    silently offering less load — while every rank trains ``trn`` (and
    a write stream into ``inf``) at a fixed step pace. Four arms:

    - ``open_loop_base``: flat offered rate inside capacity — the
      sched_ms/svc_ms pair should nearly agree, freshness lag samples
      flow (TRAFFIC-FRESH's calibration leg);
    - ``flash_crowd``: a mid-window rate spike (``crowd=``) against
      replicas=1 + a tight read SLO — the crowd must degrade to
      LATENCY (zero stale reads, zero poison, completion) while the
      burning tenant's promotion budget provably flexes ABOVE the
      configured replica count (max_budget > 1: the "replica budgets
      ride demand" acceptance);
    - ``overload_shed``: offered rate over the inf tenant's own
      admission budget — sheds land in inf's attributed counters (trn
      zero), and the burn edge leaves an ``slo_burn`` flight-recorder
      box with zero pre-arming (TRAFFIC-SHED);
    - ``idle``: the --traffic-idle-drill bitwise stamp (TRAFFIC-IDLE:
      a rate-0 armed driver schedules and issues NOTHING).

    Open-loop rates are offered, not achieved, so no arm publishes a
    throughput point — the gates read latency quantiles, freshness
    samples, budget maxima, and attributed counters (absolute checks,
    never the run-to-run ±10% comparison)."""
    import glob as _glob
    import tempfile

    from minips_tpu import launch as _launch

    t_iters = 18 if quick else 40
    warm = max(2, t_iters // 6)
    timed_s = (t_iters - warm) * 0.1     # 100ms pace, the window below
    tbase = [sys.executable, "-m", "minips_tpu.apps.sharded_ps_bench",
             "--traffic-bench", "--path", "sparse",
             "--iters", str(t_iters), "--warmup", str(warm),
             "--batch", "128", "--rows", "4096",
             # 100ms deadline pace: the timed window's wall clock IS
             # the driver's schedule horizon, so the crowd's [at,
             # at+dur) lands at a knowable second of the measurement
             "--trn-step-ms", "100",
             "--staleness", "1", "--updater", "sgd",
             "--pull-timeout", "30"]
    # replicas=1 deliberately: the flash-crowd arm's budget proof needs
    # headroom ABOVE the configured count (3 live ranks, so a burning
    # boost can grant 2 holders where calm grants 1)
    serve = ("replicas=1,hot=16,topk=64,interval=0.05,min_heat=1")
    tenant = "trn:rate=0;inf:s=2"
    # fast=2/slow=4 rolls at the 100ms tick: burn verdicts settle in
    # ~0.4s — inside even the quick arm's window
    slo = "read_ms=5,shed_rate=2,fast=2,slow=4,boost=1"
    env0 = {"MINIPS_FORCE_CPU": "1", "JAX_PLATFORMS": "cpu",
            "MINIPS_CHAOS": "", "MINIPS_RELIABLE": "1",
            "MINIPS_REBALANCE": "", "MINIPS_TRACE": "",
            "MINIPS_SERVE": "", "MINIPS_BUS": "",
            "MINIPS_WIRE_FMT": "", "MINIPS_ELASTIC": "",
            "MINIPS_CHAOS_KILL": "", "MINIPS_HEARTBEAT": "",
            "MINIPS_PUSH_COMM": "", "MINIPS_MESH": "",
            "MINIPS_AUTOSCALE": "", "MINIPS_RESHARD": "",
            "MINIPS_SLOW": "", "MINIPS_HEDGE": "",
            "MINIPS_TENANT": "", "MINIPS_SLO": "",
            "MINIPS_TRAFFIC": "", "MINIPS_FLIGHT": ""}
    grid: dict = {"iters": t_iters, "timed_s": round(timed_s, 2),
                  "serve_spec": serve, "tenant_spec": tenant,
                  "slo_spec": slo}

    def arm(traffic_spec: str, flight: str = "",
            slo_spec: str = slo, tenant_spec: str = tenant) -> dict:
        argv = list(tbase) + ["--serve", serve,
                              "--tenant", tenant_spec,
                              "--slo", slo_spec,
                              "--traffic", traffic_spec]
        env = dict(env0)
        if flight:
            env["MINIPS_FLIGHT"] = flight
        try:
            res = _launch.run_local_job(3, argv, base_port=None,
                                        env_extra=env, timeout=240.0)
        except Exception as e:  # noqa: BLE001 - completion-gated
            return {"completed": False, "error": str(e)[:300]}
        echoed = {r.get("traffic_spec") for r in res}
        assert echoed == {traffic_spec}, (traffic_spec, echoed)
        tr = [r.get("traffic") or {} for r in res]
        fresh = [r.get("freshness") or {} for r in res]
        fleet = [f.get("fleet") or {} for f in fresh]
        slo_b = [r.get("slo") or {} for r in res]
        tb = [r.get("tenant") or {} for r in res]
        rep = [(r.get("serve") or {}).get("replica") for r in res]

        def tcnt(tname: str, key: str) -> int:
            return sum(((b.get("tenants") or {}).get(tname) or {})
                       .get(key, 0) for b in tb)

        def budget_max(tname: str) -> int:
            return max((((b.get("tenants") or {}).get(tname) or {})
                        .get("max_budget", 0)) for b in slo_b)

        p99s = [((t.get("sched_ms") or {}).get("p99_ms") or 0.0)
                for t in tr]
        fp99 = [((f.get("lag") or {}).get("p99_ms") or 0.0)
                for f in fleet]
        out = {
            "completed": all(r.get("event") == "done" for r in res),
            # offered vs issued: unissued > 0 means the run ended
            # with schedule left over (a gate problem, not a shed)
            "scheduled": sum(t.get("scheduled", 0) for t in tr),
            "requests": sum(t.get("requests", 0) for t in tr),
            "unissued": sum(t.get("unissued", 0) for t in tr),
            # summed dispatcher count: the gate's stop-boundary
            # allowance (each thread abandons <= 1 claimed arrival)
            "conc": sum(t.get("conc", 0) for t in tr),
            "errors": sum(t.get("errors", 0) for t in tr),
            "late_issues": sum(t.get("late_issues", 0) for t in tr),
            # the honest tail (max across ranks): scheduled-arrival ->
            # completion, next to bare service time
            "sched_p99_ms": round(max(p99s), 3) if p99s else None,
            "svc_p99_ms": round(max(
                ((t.get("svc_ms") or {}).get("p99_ms") or 0.0)
                for t in tr), 3),
            # TRAFFIC-FRESH evidence: push-visible-at-replica lag
            "freshness_samples": sum(f.get("lag_samples", 0)
                                     for f in fleet),
            "freshness_p99_ms": round(max(fp99), 3) if fp99 else None,
            "stamped_frames": sum(f.get("stamped_frames", 0)
                                  for f in fleet),
            # SLO burn accounting + the budget-flex proof
            "slo_burns": sum(b.get("burns", 0) for b in slo_b),
            "slo_clears": sum(b.get("clears", 0) for b in slo_b),
            "boost_ticks": sum(b.get("boost_ticks", 0)
                               for b in slo_b),
            "inf_max_budget": budget_max("inf"),
            # tenant-attributed admission evidence (TRAFFIC-SHED)
            "trn_denied": (tcnt("trn", "shed")
                           + tcnt("trn", "throttle")),
            "inf_denied": (tcnt("inf", "shed")
                           + tcnt("inf", "throttle")),
            "stale_reads": (tcnt("trn", "stale_reads")
                            + tcnt("inf", "stale_reads")
                            + sum((x or {}).get("stale_reads") or 0
                                  for x in rep)),
            "trn_rows_per_sec": round(
                sum(r.get("trn_rows_per_sec", 0) for r in res), 1),
            "wire_frames_lost": sum(r.get("wire_frames_lost", 0)
                                    for r in res),
            "frames_dropped": sum(r.get("frames_dropped", 0)
                                  for r in res),
        }
        if flight:
            files = sorted(_glob.glob(os.path.join(
                flight, "flight-rank*.json")))
            burn_events = []
            for fp in files:
                with open(fp) as fh:
                    doc = json.load(fh)
                burn_events += [e.get("args", {}).get("tenant")
                                for e in doc.get("events", ())
                                if e.get("kind") == "slo_burn"]
            out["flight_dumps"] = len(files)
            out["flight_slo_burns"] = len(burn_events)
            out["flight_burn_tenants"] = sorted(
                {t for t in burn_events if t})
        return out

    # schedule shapes: per-rank offered rates (3 ranks run one driver
    # each); the crowd lands mid-window and must FIT inside it
    c_at = round(timed_s * 0.3, 2)
    c_for = round(timed_s * 0.3, 2)
    base_spec = "rate=60,users=1000000,alpha=1.2,batch=8,conc=2,seed=11"
    crowd_spec = base_spec + f",crowd={c_at}+{c_for}x8"
    # overload: offered far above the inf bucket below — rate-limited
    # admission sheds into inf's own budget, the burn edge dumps
    overload_tenant = "trn:rate=0;inf:rate=20,burst=4,s=2"
    grid["crowd"] = {"at": c_at, "for": c_for, "x": 8}
    grid["open_loop_base"] = arm(base_spec)
    grid["flash_crowd"] = arm(crowd_spec)
    with tempfile.TemporaryDirectory() as fdir:
        grid["overload_shed"] = arm(
            "rate=400,users=1000000,alpha=1.2,batch=8,conc=4,seed=13",
            flight=fdir, tenant_spec=overload_tenant)
    grid["overload_tenant_spec"] = overload_tenant
    # TRAFFIC-IDLE: rate-0 armed driver vs off, bitwise + zero issued
    try:
        proc = subprocess.run(
            [sys.executable, "-m",
             "minips_tpu.apps.sharded_ps_bench",
             "--traffic-idle-drill"],
            capture_output=True, text=True, timeout=300.0,
            cwd=os.path.dirname(os.path.abspath(__file__)),
            env={**os.environ, "MINIPS_FORCE_CPU": "1",
                 "JAX_PLATFORMS": "cpu", "MINIPS_MESH": "",
                 "MINIPS_CHAOS": "", "MINIPS_TENANT": "",
                 "MINIPS_SLO": "", "MINIPS_TRAFFIC": ""})
        res = json.loads([ln for ln in proc.stdout.splitlines()
                          if ln.startswith("{")][-1])
        grid["idle"] = {"equal": bool(res.get("bitwise_equal")),
                        "rows_checked":
                            int(res.get("rows_checked", 0)),
                        "traffic_requests":
                            res.get("traffic_requests"),
                        "traffic_scheduled":
                            res.get("traffic_scheduled")}
        if res.get("error"):
            grid["idle"]["error"] = res["error"]
    except Exception as e:  # noqa: BLE001 - the gate reads this
        grid["idle"] = {"equal": False, "rows_checked": 0,
                        "error": str(e)[:300]}
    return grid


def reshard_arms(quick: bool = False) -> dict:
    """RESHARD-MEM / RESHARD-SAFE (planned collective redistribution,
    balance/redistribute.py): the memory-bounded N->M resharding plane
    drilled four ways.

    - ``mem``: the streaming checkpoint-restore drill (mover (c)) at a
      RAM-visible table size — capped read bitwise-equal to uncapped,
      measured peak staging <= cap, legacy whole-member staging > cap.
    - ``drain_planned`` vs ``drain_p2p``: the SAME whole-rank drain
      (rank 0 hands its shard over mid-run) with the planner armed at a
      small cap vs the legacy one-shot p2p ship. Both complete bitwise;
      the planned arm's measured ``reshard.peak_stage_bytes`` stays
      under the cap while the p2p arm's ``rebalance.peak_stage_bytes``
      (the whole staged shard) provably exceeds it at the same size —
      RESHARD-MEM's live-wire leg.
    - ``kill``: seeded SIGKILL of a gainer mid-run with the planner and
      an aggressive rebalancer armed; survivors restore the dead
      ranges from the elastic checkpoint and finish with zero
      unrecovered frames and agreeing finals — RESHARD-SAFE's crash
      leg (the exact mid-round resume/abort semantics are pinned by
      tests/test_reshard.py; this arm pins process-level survival).
    - ``part``: a seeded link cut opens across the drain window
      (sender->gainer) with the reliable plane armed; the plan's slice
      rounds retransmit through the heal, everyone completes with zero
      unrecovered frames, and the post-mortem flight boxes carry the
      ``reshard_round`` evidence with ZERO pre-arming.
    """
    import glob as _glob
    import tempfile

    from minips_tpu import launch as _launch

    cap = 4096                       # bytes: far below one shard
    r_iters = 20 if quick else 30
    drain_at = 8
    base = [sys.executable, "-m",
            "minips_tpu.apps.sharded_ps_example",
            "--model", "sparse", "--mode", "ssp",
            "--staleness", "2", "--iters", str(r_iters),
            "--batch", "64", "--checkpoint-every", "5",
            "--drain-rank", "0", "--drain-at", str(drain_at)]
    env0 = {"MINIPS_FORCE_CPU": "1", "JAX_PLATFORMS": "cpu",
            "MINIPS_CHAOS": "", "MINIPS_RELIABLE": "",
            "MINIPS_REBALANCE": "", "MINIPS_TRACE": "",
            "MINIPS_SERVE": "", "MINIPS_BUS": "",
            "MINIPS_WIRE_FMT": "", "MINIPS_CHAOS_KILL": "",
            "MINIPS_HEARTBEAT": "interval=0.1,timeout=2.0",
            "MINIPS_PUSH_COMM": "", "MINIPS_MESH": "",
            "MINIPS_AUTOSCALE": "1", "MINIPS_OBS": "",
            "MINIPS_TENANT": "",
            "MINIPS_SLO": "", "MINIPS_TRAFFIC": "",
            "MINIPS_FLIGHT": "", "MINIPS_SLOW": "",
            "MINIPS_HEDGE": "", "MINIPS_ELASTIC": "1",
            "MINIPS_RESHARD": ""}
    grid: dict = {"iters": r_iters, "cap": cap,
                  "drain_at": drain_at}

    def drain_arm(extra_env: dict, flight: str = "") -> dict:
        try:
            with tempfile.TemporaryDirectory() as ck:
                rc, events = _launch.run_local_job_raw(
                    3, base + ["--checkpoint-dir", ck],
                    base_port=None, env_extra={**env0, **extra_env},
                    timeout=240.0, kill_on_failure=False)
            by_last = {r: (ev[-1] if ev else {})
                       for r, ev in enumerate(events)}
            dones = [by_last[r] for r in (1, 2)
                     if by_last[r].get("event") == "done"]
            if rc != 0 or len(dones) != 2:
                return {"completed": False,
                        "error": f"rc={rc}: {by_last}"[:400]}
            stamps = list(by_last.values())
            rsh = [d.get("reshard") for d in stamps]
            reb = [d.get("rebalance") or {} for d in stamps]
            sums = {d.get("param_sum") for d in dones}
            out = {
                "completed": True,
                "leaver_drained":
                    by_last[0].get("event") == "drained",
                "blocks_moved": sum(r.get("blocks_out", 0)
                                    for r in reb),
                # max, not sum: the cap bounds each rank's worst
                # simultaneous snapshot
                "peak_p2p": max(r.get("peak_stage_bytes", 0)
                                for r in reb),
                "wire_frames_lost": sum(
                    d.get("wire_frames_lost", 0) for d in dones),
                "finals_agree": len(sums) == 1,
            }
            if any(r is not None for r in rsh):
                live = [r for r in rsh if r]
                out["reshard"] = {
                    "plans": sum(r.get("plans", 0) for r in live),
                    "rounds": sum(r.get("rounds", 0) for r in live),
                    "slices": sum(r.get("slices", 0) for r in live),
                    "dup_slices": sum(r.get("dup_slices", 0)
                                      for r in live),
                    "aborts": sum(r.get("aborts", 0) for r in live),
                    "peak_planned": max(r.get("peak_stage_bytes", 0)
                                        for r in live),
                }
            else:
                out["reshard_absent"] = all(r is None for r in rsh)
            if flight:
                files = sorted(_glob.glob(os.path.join(
                    flight, "flight-rank*.json")))
                kinds: set = set()
                for fp in files:
                    with open(fp) as fh:
                        doc = json.load(fh)
                    kinds |= {e.get("kind")
                              for e in doc.get("events", ())}
                seen = {"reshard_round", "reshard_resume",
                        "reshard_abort"}
                out["flight_dumps"] = len(files)
                out["flight_events"] = sorted(kinds & seen)
                out["flight_events_ok"] = "reshard_round" in kinds
            return out
        except Exception as e:  # noqa: BLE001 - completion-gated
            return {"completed": False, "error": str(e)[:300]}

    # -------- the live-wire staging A/B: same drain, planner on/off
    grid["drain_planned"] = drain_arm(
        {"MINIPS_RESHARD": f"cap={cap}"})
    grid["drain_p2p"] = drain_arm({})

    # -------- kill: seeded SIGKILL of gainer rank 2 mid-run; the
    # planner and an eager rebalancer are both armed so state rounds
    # are in flight around the kill window
    kill_step = max(2, r_iters // 3)
    grid["kill_step"] = kill_step
    try:
        with tempfile.TemporaryDirectory() as ck:
            kbase = [sys.executable, "-m",
                     "minips_tpu.apps.sharded_ps_example",
                     "--model", "sparse", "--mode", "ssp",
                     "--staleness", "2", "--iters", str(r_iters),
                     "--batch", "64", "--checkpoint-every", "5",
                     "--checkpoint-dir", ck]
            rc, events = _launch.run_local_job_raw(
                3, kbase, base_port=None,
                env_extra={**env0,
                           "MINIPS_RESHARD": f"cap={cap}",
                           "MINIPS_REBALANCE":
                               ("block=2048,threshold=3,"
                                "interval=0.3,min_heat=1"),
                           "MINIPS_CHAOS_KILL":
                               f"7:rank=2,step={kill_step}",
                           "MINIPS_HEARTBEAT":
                               "interval=0.1,timeout=1.0"},
                timeout=240.0, kill_on_failure=False)
        dones = [ev[-1] for r, ev in enumerate(events)
                 if r != 2 and ev and ev[-1].get("event") == "done"]
        if len(dones) == 2:
            sums = {d.get("param_sum") for d in dones}
            grid["kill"] = {
                "completed": True,
                "blocks_restored": sum(
                    (d.get("membership") or {}).get(
                        "blocks_restored", 0) for d in dones),
                "reshard_aborts": sum(
                    (d.get("reshard") or {}).get("aborts", 0)
                    for d in dones),
                "wire_frames_lost": sum(
                    d.get("wire_frames_lost", 0) for d in dones),
                "finals_agree": len(sums) == 1,
            }
        else:
            grid["kill"] = {"completed": False,
                            "error": f"survivors rc={rc}: "
                                     f"{events}"[:300]}
    except Exception as e:  # noqa: BLE001 - completion-gated
        grid["kill"] = {"completed": False, "error": str(e)[:300]}

    # -------- part: the 0->2 link (sender -> one gainer) cut for 1s
    # across the drain window; reliable retransmits carry the slice
    # rounds through the heal; flight boxes carry the evidence
    with tempfile.TemporaryDirectory() as fdir:
        grid["part"] = drain_arm(
            {"MINIPS_RESHARD": f"cap={cap}",
             "MINIPS_RELIABLE":
                 "budget=4,backoff_ms=25,backoff_max_ms=150,"
                 "advert_ms=100",
             "MINIPS_CHAOS":
                 f"9:part=1,links=0-2,at={drain_at},for=1.0s",
             "MINIPS_FLIGHT": fdir},
            flight=fdir)

    # -------- mem: the streaming restore drill (subprocess stamp)
    try:
        proc = subprocess.run(
            [sys.executable, "-m",
             "minips_tpu.apps.sharded_ps_bench",
             "--reshard-mem-drill"],
            capture_output=True, text=True, timeout=300.0,
            cwd=os.path.dirname(os.path.abspath(__file__)),
            env={**os.environ, "MINIPS_FORCE_CPU": "1",
                 "JAX_PLATFORMS": "cpu", "MINIPS_MESH": "",
                 "MINIPS_CHAOS": "", "MINIPS_RESHARD": ""})
        res = json.loads([ln for ln in proc.stdout.splitlines()
                          if ln.startswith("{")][-1])
        grid["mem"] = {
            "equal": bool(res.get("bitwise_equal")),
            "cap": int(res.get("cap", 0)),
            "peak_planned": res.get("peak_planned"),
            "peak_p2p": res.get("peak_p2p"),
            "chunks": int(res.get("chunks", 0)),
        }
        if res.get("error"):
            grid["mem"]["error"] = res["error"]
    except Exception as e:  # noqa: BLE001 - the gate reads this
        grid["mem"] = {"equal": False, "error": str(e)[:300]}
    return grid


def hier_arms(quick: bool = False) -> dict:
    """HIER-WIN / HIER-IDLE (the two-level push tree, balance/hier.py):
    3 procs with host groups {0,1} | {2} — ranks 0 and 1 are co-host
    workers whose owner-2 slices ride the tree; rank 2 is a singleton
    (always flat, the degenerate clause). Both arms run the SAME seeded
    sparse workload under topk8:

    - ``hier``  (``group=2``):       member->leader exact contributions,
      ONE compressed frame per owner per boundary from the leader;
    - ``flat``  (``group=2,agg=0``): accounting-only — per-worker flat
      frames with the SAME per-level byte classification, so the two
      arms' ``l2_tx_bytes`` (the cross-host leader leg, summed over the
      tree ranks 0+1) are like-for-like.

    The win is overlap capture: co-host workers drawing zipf-skewed
    keys hit mostly the SAME rows, and the leader ships the union once
    instead of each worker shipping its own copy. The gate (HIER-WIN,
    ci/bench_regression.py) wants flat/hier l2 bytes >= 1.7x and the
    loss trajectories matching; the bitwise drills below are the
    exactness legs (compression off: tree == flat bit-for-bit; armed-
    idle == off bit-for-bit).

    No alternating-median reps here, deliberately: the comparison is a
    seeded BYTE count and a seeded loss stream (both bit-deterministic
    given the workload seeds), not a rows/sec timing number — the
    drifting-host honesty rules buy nothing, and rates from this sweep
    are never published as throughput points."""
    from minips_tpu import launch as _launch

    h_iters = 25 if quick else 40
    hbase = [sys.executable, "-m",
             "minips_tpu.apps.sharded_ps_example",
             "--model", "sparse", "--mode", "bsp",
             # 256 rows / batch 128 x 14 nnz: each worker's draws
             # cover most of owner 2's shard every step — the co-host
             # overlap regime the tree exists for (one union frame vs
             # two near-identical per-worker frames)
             "--dim", "256", "--batch", "128",
             "--iters", str(h_iters)]
    env0 = {"MINIPS_FORCE_CPU": "1", "JAX_PLATFORMS": "cpu",
            "MINIPS_RESHARD": "",
            "MINIPS_RELIABLE": "", "MINIPS_REBALANCE": "",
            "MINIPS_TRACE": "", "MINIPS_SERVE": "",
            "MINIPS_BUS": "", "MINIPS_WIRE_FMT": "",
            "MINIPS_CHAOS": "", "MINIPS_CHAOS_KILL": "",
            "MINIPS_MESH": "", "MINIPS_AUTOSCALE": "",
            "MINIPS_TENANT": "",
            "MINIPS_SLO": "", "MINIPS_TRAFFIC": "",
            "MINIPS_ELASTIC": "", "MINIPS_SLOW": "",
            "MINIPS_HEDGE": "", "MINIPS_OBS": "",
            "MINIPS_FLIGHT": "", "MINIPS_HEARTBEAT": "",
            "MINIPS_PUSH_COMM": "topk8"}
    grid: dict = {"iters": h_iters, "group": 2,
                  "tree_ranks": [0, 1], "owner_rank": 2}

    def arm(name: str, hier_spec: str) -> dict:
        try:
            res = _launch.run_local_job(
                3, list(hbase), base_port=None,
                env_extra={**env0, "MINIPS_HIER": hier_spec},
                timeout=240.0)
            hier = [d.get("hier") or {} for d in res]
            sums = {d.get("param_sum") for d in res}
            return {
                "completed": all(d.get("event") == "done"
                                 for d in res),
                "hier_spec": hier_spec,
                # the HIER-WIN observable: cross-host bytes/frames
                # out of the multi-rank group (ranks 0+1 — rank 2's
                # singleton sends stay flat in both arms and would
                # dilute the comparison)
                "l2_tx_bytes": sum(hier[r].get("l2_tx_bytes", 0)
                                   for r in (0, 1)),
                "l2_frames": sum(hier[r].get("l2_frames", 0)
                                 for r in (0, 1)),
                "l1_tx_bytes": sum(hier[r].get("l1_tx_bytes", 0)
                                   for r in (0, 1)),
                "agg_frames": sum(h.get("agg_frames", 0)
                                  for h in hier),
                "contribs": sum(h.get("contribs", 0) for h in hier),
                "fallbacks": sum(h.get("fallbacks", 0) for h in hier),
                # trajectory leg: same seeds, same draws — the arms'
                # loss streams must tell the same story
                "loss_first": res[0].get("loss_first"),
                "loss_last": res[0].get("loss_last"),
                "loss_last_by_rank": [d.get("loss_last") for d in res],
                "finals_agree": len(sums) == 1,
                "wire_frames_lost": sum(
                    d.get("wire_frames_lost", 0) for d in res),
            }
        except Exception as e:  # noqa: BLE001 - completion-gated
            return {"completed": False, "error": str(e)[:300]}

    grid["hier"] = arm("hier", "group=2")
    grid["flat"] = arm("flat", "group=2,agg=0")
    hb, fb = (grid["hier"].get("l2_tx_bytes") or 0,
              grid["flat"].get("l2_tx_bytes") or 0)
    grid["l2_bytes_ratio"] = round(fb / hb, 3) if hb else None

    # the exactness legs: compression-off tree bitwise == flat, and
    # armed-idle bitwise == off (subprocess drills, stamp protocol)
    def drill(flag: str) -> dict:
        try:
            proc = subprocess.run(
                [sys.executable, "-m",
                 "minips_tpu.apps.sharded_ps_bench", flag],
                capture_output=True, text=True, timeout=300.0,
                cwd=os.path.dirname(os.path.abspath(__file__)),
                env={**os.environ, "MINIPS_FORCE_CPU": "1",
                     "JAX_PLATFORMS": "cpu", "MINIPS_MESH": "",
                     "MINIPS_HIER": "", "MINIPS_PUSH_COMM": ""})
            res = json.loads([ln for ln in proc.stdout.splitlines()
                              if ln.startswith("{")][-1])
            out = {"equal": bool(res.get("bitwise_equal")),
                   "rows_checked": int(res.get("rows_checked", 0)),
                   "agg_frames": res.get("agg_frames")}
            if res.get("error"):
                out["error"] = res["error"]
            return out
        except Exception as e:  # noqa: BLE001 - the gate reads this
            return {"equal": False, "rows_checked": 0,
                    "error": str(e)[:300]}

    grid["bitwise"] = drill("--hier-bitwise-drill")
    grid["idle"] = drill("--hier-idle-drill")
    return grid


def hybrid_arms(quick: bool = False) -> dict:
    """HYBRID-WIN / HYBRID-IDLE (the hybrid data plane: the PR16 tree
    with the leader's host-side f64 dedup loop swapped for a device
    reduce over the in-host mesh, ``agg=mesh``). Three legs:

    - TIMED: the bench worker, 3 procs, the seeded zipf sparse point
      rows=128/dim=4096/batch=32 — small table, fat rows, small
      batches: the host kernel's per-dim Python bincount loop costs
      ~dim interpreter calls per owner per flush REGARDLESS of row
      count, which is exactly what one jitted segment-sum +
      reduce-scatter amortizes. f32 mesh comm (the quantizer is a net
      tax on CPU hosts — docs/architecture.md carries the caveat; on a
      real accelerator the blk8 tier is the bytes win). Alternating
      rep pairs, median of rows/sec/proc: HYBRID-WIN wants hybrid
      STRICTLY above the host-agg tree with cross-host bytes no worse
      (identical flush protocol — the reduce backend never touches the
      wire, so l2 bytes must match, not just not-regress).
    - LOSS: the example-app trajectory leg (hier_arms' convention,
      same seeds both arms) — the speed must not come from different
      math.
    - DRILLS: armed-idle (group=1,agg=mesh == off bitwise) and the
      one-device degenerate mesh (== agg=host bitwise — THE shared
      f64 kernel, deposit order preserved)."""
    from minips_tpu import launch as _launch

    reps = 2 if quick else 5
    workload = {"path": "sparse", "rows": 128, "dim": 4096,
                "batch": 32, "iters": 36, "warmup": 12,
                "key_dist": "zipf", "staleness": 2,
                "mesh_comm": "float32", "mesh_devices": 2}
    argv = [sys.executable, "-m", "minips_tpu.apps.sharded_ps_bench",
            "--path", "sparse", "--rows", "128", "--dim", "4096",
            "--batch", "32", "--iters", "36", "--warmup", "12",
            "--key-dist", "zipf", "--staleness", "2"]
    env0 = {"MINIPS_FORCE_CPU": "1", "JAX_PLATFORMS": "cpu",
            "MINIPS_RESHARD": "",
            # 2 host devices per proc: the in-host mesh the leader's
            # reduce-scatter runs over (members' slots map onto it)
            "XLA_FLAGS": "--xla_force_host_platform_device_count=2",
            "MINIPS_HIER_MESH_COMM": "float32",
            "MINIPS_HIER_MESH_DEVS": "",
            "MINIPS_RELIABLE": "", "MINIPS_REBALANCE": "",
            "MINIPS_TRACE": "", "MINIPS_SERVE": "",
            "MINIPS_BUS": "", "MINIPS_WIRE_FMT": "",
            "MINIPS_CHAOS": "", "MINIPS_CHAOS_KILL": "",
            "MINIPS_MESH": "", "MINIPS_AUTOSCALE": "",
            "MINIPS_TENANT": "",
            "MINIPS_SLO": "", "MINIPS_TRAFFIC": "",
            "MINIPS_ELASTIC": "", "MINIPS_SLOW": "",
            "MINIPS_HEDGE": "", "MINIPS_OBS": "",
            "MINIPS_FLIGHT": "", "MINIPS_HEARTBEAT": "",
            "MINIPS_PUSH_COMM": ""}

    def arm_once(hier_spec: str) -> dict:
        try:
            res = _launch.run_local_job(
                3, list(argv), base_port=None,
                env_extra={**env0, "MINIPS_HIER": hier_spec},
                timeout=240.0)
        except Exception as e:  # noqa: BLE001 - completion-gated
            return {"completed": False, "error": str(e)[:300]}
        hier = [d.get("hier") or {} for d in res]
        hyb = [d.get("hybrid") or {} for d in res]
        return {
            "completed": all(d.get("event") == "done" for d in res),
            "hier_spec": hier_spec,
            "rows_per_sec_per_process": round(statistics.mean(
                [d["rows_per_sec"] for d in res]), 1),
            # cross-host evidence: the leader leg out of the tree
            # ranks (0+1) — identical flush protocol, so the arms'
            # bytes must MATCH (the no-worse gate reads both)
            "l2_tx_bytes": sum(hier[r].get("l2_tx_bytes", 0)
                               for r in (0, 1)),
            "agg_frames": sum(h.get("agg_frames", 0) for h in hier),
            "contribs": sum(h.get("contribs", 0) for h in hier),
            "fallbacks": sum(h.get("fallbacks", 0) for h in hier),
            # hybrid-block evidence (None-vs-zeros per wire_record):
            # the mesh arm must show reduces on a REAL (>=2 device)
            # mesh with zero fallbacks/demotions; the tree arm None
            "mesh_reduces": sum(h.get("mesh_reduces", 0)
                                for h in hyb),
            "mesh_agg_fallbacks": sum(h.get("mesh_agg_fallbacks", 0)
                                      for h in hyb),
            "domain_demotions": sum(h.get("domain_demotions", 0)
                                    for h in hyb),
            "backend_mesh": max((h.get("backend_mesh", 0)
                                 for h in hyb), default=0),
            "wire_frames_lost": sum(d.get("wire_frames_lost", 0)
                                    for d in res),
        }

    # alternating rep PAIRS (the drifting-host honesty rule): each rep
    # runs tree then hybrid back-to-back, so thermal/background drift
    # taxes both arms alike; the median rep is what the gate reads
    runs: dict[str, list[dict]] = {"tree": [], "hybrid": []}
    for _ in range(reps):
        runs["tree"].append(arm_once("group=2"))
        runs["hybrid"].append(arm_once("group=2,agg=mesh"))

    def med(a: str) -> dict:
        ok = [r for r in runs[a] if r.get("completed")]
        if not ok:
            return runs[a][-1]
        by = sorted(ok, key=lambda r: r["rows_per_sec_per_process"])
        return {**by[len(by) // 2], "reps": reps}

    grid: dict = {"workload": workload, "group": 2,
                  "tree_ranks": [0, 1], "owner_rank": 2,
                  "tree": med("tree"), "hybrid": med("hybrid")}
    t, h = grid["tree"], grid["hybrid"]
    if t.get("completed") and h.get("completed"):
        grid["rows_ratio"] = round(
            h["rows_per_sec_per_process"]
            / max(t["rows_per_sec_per_process"], 1e-9), 3)

    # the trajectory leg: the example app's seeded loss stream under
    # both backends (hier_arms' convention — dim-1 table, so this leg
    # carries NO timing signal, deliberately: it answers "same math?",
    # the timed leg above answers "faster?")
    l_iters = 25 if quick else 40
    lbase = [sys.executable, "-m",
             "minips_tpu.apps.sharded_ps_example",
             "--model", "sparse", "--mode", "bsp",
             "--dim", "256", "--batch", "128",
             "--iters", str(l_iters)]

    def loss_arm(hier_spec: str) -> dict:
        try:
            res = _launch.run_local_job(
                3, list(lbase), base_port=None,
                env_extra={**env0, "MINIPS_PUSH_COMM": "topk8",
                           "MINIPS_HIER": hier_spec},
                timeout=240.0)
            sums = {d.get("param_sum") for d in res}
            return {
                "completed": all(d.get("event") == "done"
                                 for d in res),
                "loss_first": res[0].get("loss_first"),
                "loss_last": res[0].get("loss_last"),
                "finals_agree": len(sums) == 1,
                "mesh_reduces": sum((d.get("hybrid") or {}).get(
                    "mesh_reduces", 0) for d in res),
            }
        except Exception as e:  # noqa: BLE001 - completion-gated
            return {"completed": False, "error": str(e)[:300]}

    grid["loss_tree"] = loss_arm("group=2")
    grid["loss_hybrid"] = loss_arm("group=2,agg=mesh")

    # the exactness legs (subprocess drills, stamp protocol): armed-
    # idle == off bitwise; one-device degenerate mesh == host bitwise
    def drill(flag: str) -> dict:
        try:
            proc = subprocess.run(
                [sys.executable, "-m",
                 "minips_tpu.apps.sharded_ps_bench", flag],
                capture_output=True, text=True, timeout=300.0,
                cwd=os.path.dirname(os.path.abspath(__file__)),
                env={**os.environ, "MINIPS_FORCE_CPU": "1",
                     "JAX_PLATFORMS": "cpu", "MINIPS_MESH": "",
                     "MINIPS_HIER": "", "MINIPS_PUSH_COMM": "",
                     "MINIPS_HIER_MESH_DEVS": ""})
            res = json.loads([ln for ln in proc.stdout.splitlines()
                              if ln.startswith("{")][-1])
            out = {"equal": bool(res.get("bitwise_equal")),
                   "rows_checked": int(res.get("rows_checked", 0)),
                   "agg_frames": res.get("agg_frames"),
                   "mesh_reduces": res.get("mesh_reduces"),
                   "mesh_agg_fallbacks": res.get("mesh_agg_fallbacks"),
                   "domain_demotions": res.get("domain_demotions")}
            if res.get("error"):
                out["error"] = res["error"]
            return out
        except Exception as e:  # noqa: BLE001 - the gate reads this
            return {"equal": False, "rows_checked": 0,
                    "error": str(e)[:300]}

    grid["idle"] = drill("--hybrid-idle-drill")
    grid["degenerate"] = drill("--hybrid-degenerate-drill")
    return grid


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--iters", type=int, default=60)
    ap.add_argument("--quick", action="store_true",
                    help="short iters (harness validation, not numbers)")
    ap.add_argument("--trace", default=None, metavar="DIR",
                    help="artifact dir for the traced arm's per-rank "
                         "wire traces + merged_trace.json (default: a "
                         "tempdir; the merged path is recorded in the "
                         "bench JSON either way)")
    args = ap.parse_args()
    iters = 15 if args.quick else args.iters
    warmup = max(2, iters // 6)

    curve = {}  # world-size scaling, sparse path, zmq
    for n in (1, 2, 3, 4):
        curve[str(n)] = _run(n, "sparse", iters, warmup, "zmq")
    buses = {"zmq": curve["3"],
             "native": _run(3, "sparse", iters, warmup, "native")}

    # THE TRANSPORT COMPARISON (this PR): seed JSON framing over zmq vs
    # binary framing over zmq vs the shared-memory ring transport —
    # same workload, back-to-back, alternating medians (the standard
    # honesty rules on this drifting host). The claims the TRANSPORT-*
    # tripwires (ci/bench_regression.py) gate: the shm arm's rows/sec
    # strictly above zmq-json (the loopback bench finally measures
    # protocol cost, not codec cost) with bytes/row UNCHANGED across
    # arms (framing moves head bytes, never blob bytes), and the
    # compose arm — seeded chaos drop>=1% + retransmit ON the shm
    # backend — must COMPLETE with zero unrecovered frames (the
    # chaos/reliable/trace layers wrap the bus, so they must stack on
    # the new transport unchanged; its lossy-arm rate stays
    # gate-invisible like every chaos arm's).
    def _transport_arms(reps: int) -> dict:
        arms = {"zmq_json": {"bus": "zmq", "wire_fmt": "json"},
                "zmq_bin": {"bus": "zmq", "wire_fmt": "bin"},
                "shm": {"bus": "shm", "wire_fmt": "bin"}}
        runs: dict[str, list[dict]] = {a: [] for a in arms}
        for _ in range(reps):
            for a, kw in arms.items():
                runs[a].append(_run(3, "sparse", iters, warmup,
                                    kw["bus"], wire_fmt=kw["wire_fmt"]))

        def med(arm: str) -> dict:
            by = sorted(runs[arm],
                        key=lambda r: r["rows_per_sec_per_process"])
            return {**by[len(by) // 2], "reps": reps}
        grid = {a: med(a) for a in arms}
        compose = _run(3, "sparse", iters, warmup, "shm",
                       wire_fmt="bin", chaos="1234:drop=0.01,dup=0.005",
                       reliable=True, pull_timeout=8.0, may_fail=True,
                       timeout=120.0)
        if "rows_per_sec_per_process" in compose:
            # completion gate, not a comparable throughput point
            compose["rows_per_sec_lossy"] = compose.pop(
                "rows_per_sec_per_process")
        grid["shm_compose"] = compose
        return grid

    transport_grid = _transport_arms(3 if not args.quick else 1)
    paths = {"sparse": curve["3"],
             "dense": _run(3, "dense", iters, warmup, "zmq")}
    # the compressed push wire: same rows/sec workload, int8 codes on the
    # cross-process push leg — wire bytes/sec drops toward the codec
    # ratio while the pull leg is whatever --pull-wire says (f32 here).
    # Both wire comparisons measure their arms BACK-TO-BACK rather than
    # reusing curve["3"] from minutes earlier: shared-host drift would
    # otherwise dominate the rows/sec column (B/row is drift-immune, the
    # throughput comparison is not).
    wires = {"float32": _run(3, "sparse", iters, warmup, "zmq"),
             "int8": _run(3, "sparse", iters, warmup, "zmq",
                          push_comm="int8")}
    # the compressed PULL wire (this PR): pull REPLIES ship int8 codes +
    # per-row f32 scales instead of raw f32 rows — the other half of the
    # bytes/row story (the pull leg dominates sparse wire volume: reply
    # rows outweigh the 8B key slices going out)
    pull_wires = {"f32": _run(3, "sparse", iters, warmup, "zmq"),
                  "int8": _run(3, "sparse", iters, warmup, "zmq",
                               pull_wire="int8")}
    # overlapped pipeline, three arms: off (fully synchronous cycle) vs
    # pull (double-buffered prefetch only) vs on (prefetch + async ack-
    # windowed push) — the latency levers, orthogonal to the wire
    # codecs, measured in the north-star shape (--compute jit: real
    # model math between pull and push; CPU-forced so all arms run
    # identical backends). READ THE NUMBERS WITH THE HOST IN MIND: on a
    # host whose cores are OVERSUBSCRIBED by the world size (every CI
    # container this has run on so far), the sync arm's blocked time is
    # not idle — the scheduler hands it to the other processes — so
    # overlap has nothing to reclaim and its remaining cost shows as a
    # deficit: measured on 2 cores, pull ~TIES off (the prefetch is
    # near-free) while on trails by ~10-15% (the sender thread + ack
    # settling contend for the GIL/cores three ways). The lever the
    # arms prove regardless is pull_overlap_fraction: ~0 sync vs ~0.8+
    # overlapped — the pull RTT genuinely left the critical path, which
    # converts to rows/sec only where worker compute and PS serving
    # have their own hardware (real pods; an accelerator-backed
    # worker). The _fit point (min(3, cores)) pins the least-
    # oversubscribed topology this host can host so the crossover is
    # visible the day the measurement environment grows headroom.
    def _overlap_arms(n: int, reps: int) -> dict:
        # shared-CI hosts drift (cgroup bursts swing absolute rates 2-4x
        # within minutes), so one off-run vs one on-run can crown either
        # arm by luck. ALTERNATE the arms rep-by-rep — adjacent runs see
        # near-identical machine state — and report each arm's MEDIAN
        # rep, so a throttle window contaminates at most one rep of each
        # arm, never a whole arm.
        arms = {"off": {}, "pull": {"overlap": True, "overlap_legs": "pull"},
                "on": {"overlap": True}}
        runs: dict[str, list[dict]] = {a: [] for a in arms}
        for _ in range(reps):
            for a, kw in arms.items():
                runs[a].append(_run(n, "sparse", iters, warmup, "zmq",
                                    compute="jit", force_cpu=True, **kw))

        def med(arm: str) -> dict:
            by_rate = sorted(runs[arm],
                             key=lambda r: r["rows_per_sec_per_process"])
            return {**by_rate[len(by_rate) // 2], "reps": reps}
        return {a: med(a) for a in arms}

    o_reps = 1 if args.quick else 3
    over = _overlap_arms(3, o_reps)
    n_fit = min(3, os.cpu_count() or 3)
    over_fit = _overlap_arms(n_fit, o_reps) if n_fit != 3 else over

    # client row cache + deduplicated pull wire: "off" is the SEED wire
    # (duplicate keys verbatim, no cache) — the before/after this PR's
    # tentpole is judged on; "on" is unique-key wire + clock-versioned
    # row cache. The grid crosses key distribution with staleness
    # because the cache's validity window IS the staleness budget: the
    # uniform arms keep the standard 64k-row table (keys essentially
    # never recur — the no-win control, dedup/locality only), the zipf
    # arms shrink the table to the HOT WORKING SET a zipf(1.1) head
    # concentrates on, so re-draws land within the staleness window.
    # Same alternating-median honesty rules as the overlap sweep.
    # Fixed knobs: sgd updater + f32 push wire (the write-through
    # regime — adagrad/adam invalidate on push, pinning hit rate to ~0
    # in a pull+push cycle; see docs/consistency.md); cache ample (no
    # LRU pressure — the byte bound has its own tests). READ THE
    # ROWS/SEC COLUMN WITH THE HOST IN MIND (the overlap sweep's
    # caveat, again): on this CPU-loopback container wire bytes are
    # memcpys — shipping 5x the rows costs almost nothing — so the
    # on-arm's saved bytes buy no wall-clock, while its bursty misses
    # (same-step fills share a stamp and expire TOGETHER) hit the
    # owner park / gate wake instead of riding an amortized stream:
    # measured medians put the zipf on-arm ~5-15% under the off-arm
    # at s>=1 (with --compute jit filling the freed time the arms tie
    # within drift). The levers this sweep PROVES are hit rate > 0
    # rising with s (the staleness budget buying locality) and
    # B/row-moved down ~84% on zipf — the currency that converts to
    # rows/sec exactly where the wire is a real network or the worker
    # has its own compute, the deployments the north star names.
    ZIPF_ROWS, CACHE_BYTES = 2048, 1 << 22

    def _cache_arms(reps: int) -> dict:
        arms = {"off": {"cache_bytes": 0, "pull_dedup": False,
                        "push_dedup": False},  # = the full seed wire
                "on": {"cache_bytes": CACHE_BYTES}}
        dists = {"uniform": None, "zipf": ZIPF_ROWS}  # dist -> rows
        runs: dict[tuple, list[dict]] = {}
        for _ in range(reps):
            for dist, rows in dists.items():
                for s in (0, 1, 2):
                    for a, kw in arms.items():
                        runs.setdefault((dist, s, a), []).append(
                            _run(3, "sparse", iters, warmup, "zmq",
                                 key_dist=dist, staleness=s,
                                 rows=rows, updater="sgd", **kw))
        grid: dict = {"zipf_rows": ZIPF_ROWS, "cache_bytes": CACHE_BYTES}
        for (dist, s, a), rs in runs.items():
            by = sorted(rs, key=lambda r: r["rows_per_sec_per_process"])
            point = {**by[len(by) // 2], "reps": reps}
            grid.setdefault(dist, {}).setdefault(f"s{s}", {})[a] = point
        return grid

    cache_grid = _cache_arms(o_reps)

    # THE COMPRESSED PUSH WIRE (this PR): the wire ladder's sparse tiers
    # measured where they earn their keep — the zipf HOT-SET workload
    # (same shape as the cache sweep's zipf arms: hot rows re-drawn
    # every step) under SSP(1), sgd. Arms: f32 (seed), int8 (per-row
    # absmax), topk8/topk4 (sparse top-k index+code streams with
    # blockwise sub-8-bit quantization + error-feedback residuals,
    # train/sharded_ps.ResidualStore). The number the WIRE-BYTES
    # tripwire (ci/bench_regression.py) gates is PUSH bytes/row-moved:
    # topk8 must beat int8 by >= 2x — rows/sec columns carry the same
    # CPU-loopback caveat as the cache sweep (saved bytes are memcpys
    # here; the byte lever converts to wall-clock on a real wire).
    # WIRE-CONVERGE gates the convergence drill below: error feedback
    # must pin the lr loss trajectory to the dense wire within
    # tolerance, with zero residual mass stranded at finalize.
    def _wire_comp_arms(reps: int) -> dict:
        arms = {"f32": {}, "int8": {"push_comm": "int8"},
                "topk8": {"push_comm": "topk8"},
                "topk4": {"push_comm": "topk4"}}
        runs: dict[str, list[dict]] = {a: [] for a in arms}
        for _ in range(reps):
            for a, kw in arms.items():
                runs[a].append(_run(3, "sparse", iters, warmup, "zmq",
                                    key_dist="zipf", staleness=1,
                                    rows=ZIPF_ROWS, updater="sgd",
                                    **kw))

        def med(arm: str) -> dict:
            by = sorted(runs[arm],
                        key=lambda r: r["rows_per_sec_per_process"])
            return {**by[len(by) // 2], "reps": reps}
        grid: dict = {"zipf_rows": ZIPF_ROWS}
        grid.update({a: med(a) for a in arms})
        grid["converge"] = _wire_converge()
        return grid

    def _wire_converge() -> dict:
        """The convergence drill arm (WIRE-CONVERGE): the sparse-LR
        example at SSP(1), dense wire vs topk8 + error feedback —
        completion-gated (no rows/sec key), the gate compares final
        losses and asserts zero resident residual mass at exit."""
        from minips_tpu import launch as _launch

        e_iters = 15 if args.quick else 40
        base = [sys.executable, "-m",
                "minips_tpu.apps.sharded_ps_example",
                "--model", "sparse", "--mode", "ssp",
                "--staleness", "1", "--iters", str(e_iters),
                "--batch", "256", "--updater", "sgd"]
        env0 = {"MINIPS_FORCE_CPU": "1", "JAX_PLATFORMS": "cpu",
                "MINIPS_RESHARD": "",
                "MINIPS_CHAOS": "", "MINIPS_RELIABLE": "",
                "MINIPS_REBALANCE": "", "MINIPS_TRACE": "",
                "MINIPS_SERVE": "", "MINIPS_BUS": "",
                "MINIPS_WIRE_FMT": "", "MINIPS_ELASTIC": "",
                "MINIPS_CHAOS_KILL": "", "MINIPS_HEARTBEAT": "",
                "MINIPS_PUSH_COMM": "", "MINIPS_MESH": "",
                "MINIPS_TENANT": "",
            "MINIPS_SLO": "", "MINIPS_TRAFFIC": ""}
        out: dict = {"iters": e_iters}
        for arm, comm in (("f32", "float32"), ("topk8", "topk8")):
            try:
                res = _launch.run_local_job(
                    3, base + ["--push-comm", comm], base_port=None,
                    env_extra=env0, timeout=240.0)
                losses = [r.get("loss_last") for r in res
                          if r.get("loss_last") is not None]
                fps = {r.get("param_fingerprint") for r in res}
                efs = [r.get("ef") for r in res]
                out[arm] = {
                    "completed": True,
                    "loss_last": max(losses) if losses else None,
                    "finals_agree": len(fps) <= 1,
                    "ef_resident_rows": sum(
                        (e or {}).get("resident_rows", 0)
                        for e in efs),
                    "wire_frames_lost": sum(
                        r.get("wire_frames_lost", 0) for r in res),
                }
            except Exception as e:  # noqa: BLE001 - completion-gated
                out[arm] = {"completed": False, "error": str(e)[:300]}
        return out

    wire_comp_grid = _wire_comp_arms(o_reps)

    # chaos resilience (this PR): seeded frame loss on the live wire,
    # drop ∈ {0, 1%, 5%} × retransmit on/off, against a clean reference.
    # The claims each arm pins: "clean" vs "drop0_on" bounds the reliable
    # layer's TAX on a lossless wire (ci/bench_regression CHAOS-TAX
    # tripwire: must stay within slack); the drop>0 "_on" arms must
    # COMPLETE with zero unrecovered loss (rows/sec > 0 — loss became
    # latency); the drop>0 "_off" arms are EXPECTED to die through the
    # existing poison path (recorded as completed=False, rate 0 — the
    # honest before/after of the retransmit protocol). Short pull
    # deadline so the off arms die in seconds, not the default minute.
    def _chaos_arms(reps: int) -> dict:
        grid: dict = {"drop_rates": {"drop1": 0.01, "drop5": 0.05},
                      "seed": 1234}
        # the CHAOS-TAX pair (clean vs drop0_on) is a throughput
        # COMPARISON, so it gets the same alternating-median treatment
        # as the overlap/cache sweeps — adjacent reps see near-identical
        # machine state, and a single-run pair on this drifting host has
        # crowned either arm by 2x in both directions
        pair = {"clean": {}, "drop0_on": {"chaos": "1234:drop=0",
                                          "reliable": True}}
        runs: dict[str, list[dict]] = {a: [] for a in pair}
        for _ in range(reps):
            for a, kw in pair.items():
                runs[a].append(_run(3, "sparse", iters, warmup, "zmq",
                                    pull_timeout=8.0, **kw))
        for a in pair:
            by = sorted(runs[a],
                        key=lambda r: r["rows_per_sec_per_process"])
            grid[a] = {**by[len(by) // 2], "reps": reps}
        # the drop>0 arms are COMPLETION gates (on must finish clean,
        # off is expected to die) — one run each is the measurement
        arms = [("drop0_off", 0.0, False)]
        for label, rate in (("drop1", 0.01), ("drop5", 0.05)):
            arms += [(f"{label}_on", rate, True),
                     (f"{label}_off", rate, False)]
        for arm, rate, rel in arms:
            res = _run(3, "sparse", iters, warmup, "zmq",
                       chaos=f"1234:drop={rate}", reliable=rel,
                       pull_timeout=8.0,
                       may_fail=rate > 0, timeout=120.0)
            if rate > 0 and res.get("completed"):
                # drop>0 arms are COMPLETION gates, not comparable
                # throughput points: single runs under active loss (on)
                # or lucky survivals (off) must not enter the run-to-run
                # ±10% REGRESSED/MISSING gate — their rate lives under a
                # gate-invisible key (CHAOS-DEAD checks it absolutely)
                key = ("rows_per_sec_lossy" if rel
                       else "rows_per_sec_survived")
                res[key] = res.pop("rows_per_sec_per_process")
            grid[arm] = res
        return grid

    chaos_grid = _chaos_arms(o_reps)

    # heat-aware rebalancing (this PR): UNPERMUTED zipf(1.1) — the whole
    # head inside shard 0's range, the pathology the permuted default
    # hides — static partition vs MINIPS_REBALANCE on, SSP(1). These are
    # IMBALANCE/COMPLETION gates, not throughput comparisons: a skewed
    # arm's rows/sec is one hot owner's serial serve rate and swings
    # with scheduling luck, so it lives under a gate-invisible key
    # (rows_per_sec_skewed) exactly like the chaos arms' — the numbers
    # the REBAL-SKEW tripwire (ci/bench_regression.py) gates are
    # serve_load_imbalance (max/mean per-shard serve rows: rebalance arm
    # strictly below static), migrations >= 1, and zero drops/losses.
    # The permuted arm rides along as the balanced reference point.
    REBAL_SPEC = ("interval=0.25,threshold=1.2,max_blocks=16,"
                  "block=16,topk=64")

    def _rebalance_arms() -> dict:
        grid: dict = {"spec": REBAL_SPEC}
        arms = {
            "permuted": {"key_dist": "zipf"},
            "static": {"key_dist": "zipf", "zipf_permute_hot": False},
            "rebalance": {"key_dist": "zipf", "zipf_permute_hot": False,
                          "rebalance": REBAL_SPEC},
        }
        for name, kw in arms.items():
            # skewed arms record failure as completed=False (the
            # REBAL-DEAD tripwire's input) instead of killing the whole
            # artifact — same contract as the chaos arms
            res = _run(3, "sparse", iters, warmup, "zmq", staleness=1,
                       may_fail=(name != "permuted"), timeout=240.0,
                       **kw)
            if name != "permuted" and "rows_per_sec_per_process" in res:
                res["rows_per_sec_skewed"] = res.pop(
                    "rows_per_sec_per_process")
            grid[name] = res
        return grid

    rebalance_grid = _rebalance_arms()

    # wire tracing (this PR): the TRACE-TAX pair — untraced vs
    # MINIPS_TRACE-armed, same workload, alternating-median like every
    # other throughput comparison on this drifting host. The traced
    # arm's per-rank Chrome traces land in the artifact dir
    # (--trace, default a tempdir), the merge CLI combines them, and
    # the merged path + flow-link count ride the bench JSON — the
    # ci/bench_regression TRACE-TAX/TRACE-MERGE tripwires gate both
    # (tracing may not tax the wire beyond 15%, and the traces it
    # pays for must actually merge with >= 1 cross-rank flow).
    def _trace_arms(reps: int) -> dict:
        import tempfile

        trace_root = args.trace or tempfile.mkdtemp(
            prefix="minips-trace-")
        trace_dir = os.path.join(trace_root, "traced_3proc")
        arms = {"untraced": {}, "traced": {"trace": trace_dir}}
        runs: dict[str, list[dict]] = {a: [] for a in arms}
        for _ in range(reps):
            for a, kw in arms.items():
                runs[a].append(_run(3, "sparse", iters, warmup, "zmq",
                                    staleness=1, **kw))

        def med(arm: str) -> dict:
            by = sorted(runs[arm],
                        key=lambda r: r["rows_per_sec_per_process"])
            return {**by[len(by) // 2], "reps": reps}
        grid = {a: med(a) for a in arms}
        # merge the LAST rep's per-rank traces (each rep's dump
        # overwrites rank-wise: one coherent set remains)
        merged_path = os.path.join(trace_dir, "merged_trace.json")
        proc = subprocess.run(
            [sys.executable, "-m", "minips_tpu.obs.merge", trace_dir,
             "-o", merged_path],
            capture_output=True, text=True, timeout=120.0)
        summary = {}
        if proc.returncode == 0:
            try:
                summary = json.loads(proc.stdout.splitlines()[-1])
            except (json.JSONDecodeError, IndexError):
                pass
        grid["traced"].update({
            "trace_dir": trace_dir,
            "merged_trace": merged_path if proc.returncode == 0
            else None,
            "merge_ok": proc.returncode == 0,
            "flows_linked": summary.get("flows_linked", 0),
        })
        return grid

    trace_grid = _trace_arms(o_reps)

    # ALWAYS-ON OBSERVABILITY TAX (this PR): the windowed-metrics layer
    # + flight recorder are on by DEFAULT, so unlike TRACE-TAX (where
    # the armed arm is the special one) here the DEFAULT arm is the
    # measured product and the off arm (MINIPS_OBS=0 MINIPS_FLIGHT=0)
    # exists only to price it. Same alternating-median honesty rules;
    # the ci/bench_regression OBS-TAX tripwire holds the on arm within
    # the TRACE-TAX-style band of off.
    def _obs_tax_arms(reps: int) -> dict:
        arms = {"obs_off": {"obs": "0", "flight": "0"}, "obs_on": {}}
        runs: dict[str, list[dict]] = {a: [] for a in arms}
        for _ in range(reps):
            for a, kw in arms.items():
                runs[a].append(_run(3, "sparse", iters, warmup, "zmq",
                                    staleness=1, **kw))

        def med(arm: str) -> dict:
            by = sorted(runs[arm],
                        key=lambda r: r["rows_per_sec_per_process"])
            return {**by[len(by) // 2], "reps": reps}
        return {a: med(a) for a in arms}

    obs_tax_grid = _obs_tax_arms(o_reps)

    # THE PULL STORM (this PR): the PS measured as a SERVICE — 6 read-
    # only clients (2 threads x 3 ranks) firing request-sized zipf
    # reads (8 keys: a user lookup, not a training batch) against 1
    # pusher, unpermuted zipf(1.1) so the hot head sits in shard 0.
    # Arms: replicas OFF (every hot read pays a wire RTT to the one
    # hot owner) vs the serving plane ON (owners promote the warm
    # working set to replica ranks; a reader holding a replica serves
    # hot keys LOCALLY, zero wire) vs SHED (admission rate throttled
    # so the owner sheds/backpressures — the refuse-with-retry path
    # must complete, never poison). Alternating medians like every
    # throughput pair. Storm rates live under gate-invisible keys
    # (read_rows_per_sec) — the absolute SERVE-* tripwires in
    # ci/bench_regression.py gate them, not the ±10% run-to-run
    # comparison (the off arm is one hot owner's serve rate, which
    # swings like the rebalance static arm). HONESTY NOTE (the PR1
    # overlap caveat again): on this 2-core container both arms'
    # latency TAILS are scheduler noise that swings integer factors
    # run to run — reads/sec and p50 separate the arms robustly
    # (local replica hits are ~free), p99 only within a slack band.
    STORM_SPEC = ("replicas=2,hot=512,interval=0,min_heat=0.5,"
                  "decay=0.9,lease=2.0")
    STORM_SHED_SPEC = STORM_SPEC + ",rate=50,burst=4"

    def _storm_args() -> list:
        return ["--storm", "2", "--storm-pushers", "1",
                "--storm-batch", "8", "--storm-think-ms", "2",
                "--storm-step-s", "0.03", "--batch", "128",
                "--rows", "4096", "--key-dist", "zipf",
                "--no-zipf-permute-hot", "--staleness", "1",
                "--updater", "sgd", "--pull-timeout", "30"]

    def _run_storm(serve: str | None, iters_s: int,
                   timeout: float = 240.0) -> dict:
        argv = [sys.executable, "-m", "minips_tpu.apps.sharded_ps_bench",
                "--path", "sparse", "--iters", str(iters_s),
                "--warmup", str(max(2, iters_s // 6))] \
            + _storm_args()
        if serve:
            argv += ["--serve", serve]
        from minips_tpu import launch

        try:
            res = launch.run_local_job(
                3, argv, base_port=None,
                env_extra={"MINIPS_CHAOS": "", "MINIPS_RELIABLE": "",
                           "MINIPS_REBALANCE": "", "MINIPS_TRACE": "",
                           "MINIPS_SERVE": "", "MINIPS_BUS": "",
                           "MINIPS_WIRE_FMT": "", "MINIPS_ELASTIC": "",
                           "MINIPS_CHAOS_KILL": "",
                           "MINIPS_HEARTBEAT": "",
                           "MINIPS_PUSH_COMM": "", "MINIPS_MESH": "",
                           "MINIPS_TENANT": "",
            "MINIPS_SLO": "", "MINIPS_TRAFFIC": ""},
                timeout=timeout)
        except Exception as e:  # noqa: BLE001 - completion-gated arms
            return {"completed": False, "error": str(e)[:300]}
        echoed_sv = {r.get("serve_spec") for r in res}
        assert echoed_sv == {serve or None}, (serve, echoed_sv)
        rep = [r["serve"]["replica"] for r in res]

        def tot(k: str) -> int:
            return sum((x or {}).get(k) or 0 for x in rep)
        hists = [r["hist"]["pull_latency_ms"] or {} for r in res]
        out = {
            "completed": True,
            "read_rows_per_sec": round(
                sum(r["read_rows_per_sec"] for r in res), 1),
            "pull_p50_ms": max((h.get("p50_ms") or 0.0)
                               for h in hists),
            "pull_p99_ms": max((h.get("p99_ms") or 0.0)
                               for h in hists),
            "wire_frames_lost": sum(r["wire_frames_lost"]
                                    for r in res),
            "frames_dropped": sum(r["frames_dropped"] for r in res),
        }
        if serve:
            out.update({
                "replica_local_rows": tot("replica_local_rows"),
                "replica_wire_rows": tot("replica_served_rows"),
                "stale_reads": tot("stale_reads"),
                "shed_redirects": tot("shed_redirects"),
                "backpressure": tot("backpressure"),
                "lease_refused": (tot("lease_refused")
                                  + tot("stale_refused")),
            })
        return out

    def _storm_grid(reps: int) -> dict:
        s_iters = 15 if args.quick else 60
        arms = {"off": None, "on": STORM_SPEC}
        runs: dict[str, list[dict]] = {a: [] for a in arms}
        for _ in range(reps):
            for a, spec in arms.items():
                runs[a].append(_run_storm(spec, s_iters))

        def med(arm: str) -> dict:
            ok = [r for r in runs[arm] if r.get("completed")]
            if not ok:
                return runs[arm][-1]
            by = sorted(ok, key=lambda r: r["read_rows_per_sec"])
            return {**by[len(by) // 2], "reps": reps}
        grid = {"spec": STORM_SPEC, "off": med("off"), "on": med("on")}
        # the shed arm is a COMPLETION gate (SERVE-SHED): with the
        # admission bucket throttled the run must still finish —
        # refusals become explicit redirects/backoffs, never timeouts
        grid["shed"] = _run_storm(STORM_SHED_SPEC, s_iters)
        grid["shed"]["spec"] = STORM_SHED_SPEC
        return grid

    storm_grid = _storm_grid(o_reps)

    # ELASTIC MEMBERSHIP (this PR): the join/leave/death state machine
    # (balance/membership.py) drilled as bench arms on the example app
    # (it owns the checkpoint/recovery protocol the death path needs).
    # These are COMPLETION gates, not throughput comparisons — the
    # kill arm's wall-clock contains a heartbeat-detection stall and
    # the join arm changes world size mid-run, so no arm carries
    # rows_per_sec_per_process (steps/sec rides a gate-invisible key,
    # the PR3 lossy-arm convention). The ci/bench_regression ELASTIC-*
    # tripwires gate: ELASTIC-DEAD — the seeded-SIGKILL arm's
    # survivors complete with >= 1 range restored from the elastic
    # checkpoint, zero unrecovered frames, and a finite final loss;
    # ELASTIC-JOIN — the standby-admission arm completes with the
    # joiner serving > 0 rows.
    def _elastic_arms() -> dict:
        import tempfile

        from minips_tpu import launch as _launch

        e_iters = 15 if args.quick else 30
        base = [sys.executable, "-m",
                "minips_tpu.apps.sharded_ps_example",
                "--model", "sparse", "--mode", "ssp",
                "--staleness", "2", "--iters", str(e_iters),
                "--batch", "128", "--checkpoint-every", "5"]
        env0 = {"MINIPS_FORCE_CPU": "1", "JAX_PLATFORMS": "cpu",
                "MINIPS_RESHARD": "",
                "MINIPS_CHAOS": "", "MINIPS_RELIABLE": "",
                "MINIPS_REBALANCE": "", "MINIPS_TRACE": "",
                "MINIPS_SERVE": "", "MINIPS_BUS": "",
                "MINIPS_WIRE_FMT": "", "MINIPS_CHAOS_KILL": "",
                "MINIPS_HEARTBEAT": "", "MINIPS_PUSH_COMM": "",
                "MINIPS_MESH": "", "MINIPS_AUTOSCALE": "",
            "MINIPS_TENANT": "",
            "MINIPS_SLO": "", "MINIPS_TRAFFIC": "",
                "MINIPS_OBS": "", "MINIPS_FLIGHT": ""}
        kill_step = max(2, e_iters // 3)
        grid: dict = {"iters": e_iters, "kill_step": kill_step}

        def summarize(dones: list[dict]) -> dict:
            sums = [d.get("param_sum") for d in dones]
            losses = [d.get("loss_last") for d in dones
                      if d.get("loss_last") is not None]
            mships = [d.get("membership") or {} for d in dones]
            return {
                "completed": True,
                "steps_per_sec_elastic": round(
                    e_iters / max(max(d["wall_s"] for d in dones),
                                  1e-9), 2),
                "wire_frames_lost": sum(d.get("wire_frames_lost", 0)
                                        for d in dones),
                "frames_dropped": sum(d.get("frames_dropped", 0)
                                      for d in dones),
                "loss_last": max(losses) if losses else None,
                "blocks_restored": sum(m.get("blocks_restored", 0)
                                       for m in mships),
                "finals_agree": len({s for s in sums
                                     if s is not None}) <= 1,
            }

        # -------- steady: armed but idle — the plane's tax must be
        # invisible (the bitwise lockstep drill pins the numerics;
        # this arm pins that an armed fleet completes cleanly)
        with tempfile.TemporaryDirectory() as ck:
            try:
                res = _launch.run_local_job(
                    3, base + ["--checkpoint-dir", ck],
                    base_port=None,
                    env_extra={**env0, "MINIPS_ELASTIC": "1"},
                    timeout=240.0)
                grid["steady"] = summarize(res)
            except Exception as e:  # noqa: BLE001 - completion-gated
                grid["steady"] = {"completed": False,
                                  "error": str(e)[:300]}
        # -------- kill: seeded SIGKILL of rank 2 mid-run; survivors
        # restore its ranges from the elastic checkpoint and finish
        with tempfile.TemporaryDirectory() as ck:
            try:
                rc, events = _launch.run_local_job_raw(
                    3, base + ["--checkpoint-dir", ck],
                    base_port=None,
                    env_extra={**env0, "MINIPS_ELASTIC": "1",
                               "MINIPS_CHAOS_KILL":
                                   f"7:rank=2,step={kill_step}",
                               "MINIPS_HEARTBEAT":
                                   "interval=0.1,timeout=1.0"},
                    timeout=240.0, kill_on_failure=False)
                dones = [ev[-1] for r, ev in enumerate(events)
                         if r != 2 and ev
                         and ev[-1].get("event") == "done"]
                if len(dones) == 2:
                    grid["kill"] = summarize(dones)
                else:
                    grid["kill"] = {"completed": False,
                                    "error": f"survivors rc={rc}: "
                                             f"{events}"[:300]}
            except Exception as e:  # noqa: BLE001 - completion-gated
                grid["kill"] = {"completed": False,
                                "error": str(e)[:300]}
        # -------- join: 3 live + 1 standby admitted mid-run; the
        # joiner must end OWNING blocks and SERVING pulls
        with tempfile.TemporaryDirectory() as ck:
            try:
                res = _launch.run_local_job(
                    4, base + ["--checkpoint-dir", ck, "--join-at",
                               str(kill_step)],
                    base_port=None,
                    env_extra={**env0, "MINIPS_ELASTIC": "live=0-2"},
                    timeout=240.0)
                point = summarize(res)
                joiner = res[3].get("serve") or {}
                point["joiner_serve_rows"] = joiner.get("pull_rows", 0)
                point["joiner_serve_requests"] = joiner.get(
                    "pull_requests", 0)
                grid["join"] = point
            except Exception as e:  # noqa: BLE001 - completion-gated
                grid["join"] = {"completed": False,
                                "error": str(e)[:300]}
        return grid

    elastic_grid = _elastic_arms()

    # PRODUCTION CONTROL PLANE (this PR): the coordinator LEASE
    # (balance/control_plane.py) + the closed-loop autoscaler
    # (balance/autoscaler.py), drilled as three COMPLETION arms on the
    # example app. Rates ride the gate-invisible ``steps_per_sec_ctrl``
    # key (the chaos-arm convention — the kill arm's wall contains a
    # detection stall and the storm arm changes world size mid-run).
    # The ci/bench_regression CTRL-* tripwires gate: CTRL-FAILOVER —
    # the rank-0 (lease holder) seeded-SIGKILL arm's survivors finish
    # the FULL step count with the lease advanced exactly once, >= 1
    # range restored, zero unrecovered frames, bitwise agreement;
    # CTRL-SCALE — the storm arm completes with >= 1 autoscaler admit
    # and >= 1 drain and post-admit shed rate at or below pre-admit;
    # the steady armed-idle arm completes with zero membership changes.
    def _control_plane_arms() -> dict:
        import tempfile

        from minips_tpu import launch as _launch

        c_iters = 20 if args.quick else 40
        base = [sys.executable, "-m",
                "minips_tpu.apps.sharded_ps_example",
                "--model", "sparse", "--mode", "ssp",
                "--staleness", "2", "--iters", str(c_iters),
                "--batch", "128", "--checkpoint-every", "5"]
        env0 = {"MINIPS_FORCE_CPU": "1", "JAX_PLATFORMS": "cpu",
                "MINIPS_RESHARD": "",
                "MINIPS_CHAOS": "", "MINIPS_RELIABLE": "",
                "MINIPS_REBALANCE": "", "MINIPS_TRACE": "",
                "MINIPS_SERVE": "", "MINIPS_BUS": "",
                "MINIPS_WIRE_FMT": "", "MINIPS_CHAOS_KILL": "",
                "MINIPS_HEARTBEAT": "", "MINIPS_PUSH_COMM": "",
                "MINIPS_MESH": "", "MINIPS_AUTOSCALE": "",
            "MINIPS_TENANT": "",
            "MINIPS_SLO": "", "MINIPS_TRAFFIC": "",
                "MINIPS_OBS": "", "MINIPS_FLIGHT": ""}
        grid: dict = {"iters": c_iters}

        def rate(dones: list[dict]) -> float:
            return round(c_iters / max(max(d["wall_s"] for d in dones),
                                       1e-9), 2)

        # -------- steady: lease + autoscaler armed, zero load — must
        # complete with ZERO membership changes (hysteresis honesty;
        # the in-proc lockstep drill pins the numerics bitwise)
        with tempfile.TemporaryDirectory() as ck:
            try:
                res = _launch.run_local_job(
                    3, base + ["--checkpoint-dir", ck],
                    base_port=None,
                    env_extra={**env0, "MINIPS_ELASTIC": "1",
                               "MINIPS_AUTOSCALE": "1"},
                    timeout=240.0)
                mships = [d.get("membership") or {} for d in res]
                ascale = [d.get("autoscale") or {} for d in res]
                grid["steady"] = {
                    "completed": True,
                    "steps_per_sec_ctrl": rate(res),
                    "joins": sum(m.get("joins", 0) for m in mships),
                    "leaves": sum(m.get("leaves", 0) for m in mships),
                    "admits": sum(a.get("admits", 0) for a in ascale),
                    "drains": sum(a.get("drains", 0) for a in ascale),
                    "wire_frames_lost": sum(
                        d.get("wire_frames_lost", 0) for d in res),
                }
            except Exception as e:  # noqa: BLE001 - completion-gated
                grid["steady"] = {"completed": False,
                                  "error": str(e)[:300]}
        # -------- kill: seeded SIGKILL of RANK 0, the lease holder.
        # Survivors must elect rank 1 exactly once (every done line's
        # lease term == 1), restore the corpse's ranges, and lose no
        # step — the anti-SPOF acceptance.
        kill_step = max(8, c_iters // 3)
        with tempfile.TemporaryDirectory() as ck:
            try:
                # the flight recorder is ALWAYS ON — the kill arm only
                # pins its dump DIR so the FLIGHT-DUMP gate can count
                # the survivors' black boxes and run the merge CLI on
                # them (the gate's whole claim: a chaos kill leaves a
                # post-mortem artifact with zero pre-arming)
                fdir = os.path.join(ck, "flight")
                rc, events = _launch.run_local_job_raw(
                    3, base + ["--checkpoint-dir", ck],
                    base_port=None,
                    env_extra={**env0, "MINIPS_ELASTIC": "1",
                               "MINIPS_FLIGHT": fdir,
                               "MINIPS_CHAOS_KILL":
                                   f"7:rank=0,step={kill_step}",
                               "MINIPS_HEARTBEAT":
                                   "interval=0.1,timeout=1.0"},
                    timeout=240.0, kill_on_failure=False)
                import glob as _glob

                flight_files = sorted(_glob.glob(
                    os.path.join(fdir, "flight-rank*.json")))
                fproc = subprocess.run(
                    [sys.executable, "-m", "minips_tpu.obs.flight",
                     fdir], capture_output=True, text=True,
                    timeout=60.0)
                dones = [ev[-1] for r, ev in enumerate(events)
                         if r != 0 and ev
                         and ev[-1].get("event") == "done"]
                if len(dones) == 2:
                    terms = [((d.get("membership") or {}).get("lease")
                              or {}).get("term") for d in dones]
                    sums = {d.get("param_sum") for d in dones}
                    grid["kill"] = {
                        "completed": True,
                        "steps_per_sec_ctrl": rate(dones),
                        "lease_term": max(t for t in terms
                                          if t is not None),
                        "terms_agree": len(set(terms)) == 1,
                        "clock_min": min(d["clock"] for d in dones),
                        "iters": c_iters,
                        "blocks_restored": sum(
                            (d.get("membership") or {}).get(
                                "blocks_restored", 0) for d in dones),
                        "wire_frames_lost": sum(
                            d.get("wire_frames_lost", 0)
                            for d in dones),
                        "finals_agree": len(sums) == 1,
                        # FLIGHT-DUMP gate inputs: >= 1 valid dump per
                        # survivor (the SIGKILLed rank 0 leaves none —
                        # nothing can) and the merge CLI exits 0
                        "flight_dumps": len(flight_files),
                        "flight_merge_ok": fproc.returncode == 0,
                    }
                else:
                    grid["kill"] = {"completed": False,
                                    "error": f"survivors rc={rc}: "
                                             f"{events}"[:300]}
            except Exception as e:  # noqa: BLE001 - completion-gated
                grid["kill"] = {"completed": False,
                                "error": str(e)[:300]}
        # -------- storm: 3 live + 1 held standby; a pull storm trips
        # admission shedding at the hot owner, the autoscaler admits
        # the standby under load (heat-aware placement), the storm ebbs
        # and the autoscaler drains its own growth — the closed loop.
        s_from = 4 if args.quick else 8
        s_until = (c_iters - 10) if args.quick else (c_iters - 14)
        with tempfile.TemporaryDirectory() as ck:
            try:
                res = _launch.run_local_job(
                    4, base + ["--checkpoint-dir", ck,
                               # pace the fleet so the serve rate below
                               # clears steady traffic on any host —
                               # only the storm sheds, so the drain's
                               # calm streak is clean calm
                               "--slow-rank", "1", "--slow-ms", "15",
                               "--storm-from", str(s_from),
                               "--storm-until", str(s_until),
                               # 12 pulls/step: the 3-rank storm sheds
                               # decisively at any step rate above
                               # ~6/s against rate=200, while steady
                               # traffic (3 legs/step/owner) stays
                               # inside the bucket up to the pacing cap
                               "--storm-pulls", "12",
                               "--storm-keys", "64"],
                    base_port=None,
                    env_extra={**env0, "MINIPS_ELASTIC": "live=0-2",
                               "MINIPS_AUTOSCALE":
                                   "up_shed=4,up_after=2,"
                                   "down_after=4,cool=2",
                               "MINIPS_SERVE":
                                   "rate=200,burst=16,min_heat=1e9"},
                    timeout=300.0)
                dones = [d for d in res if d.get("event") == "done"]
                ascale = [d.get("autoscale") or {} for d in res]
                pre = [a.get("shed_rate_pre") for a in ascale
                       if a.get("shed_rate_pre") is not None]
                post = [a.get("shed_rate_post") for a in ascale
                        if a.get("shed_rate_post") is not None]
                grid["storm"] = {
                    "completed": len(dones) == 3,
                    "steps_per_sec_ctrl": rate(dones) if dones else None,
                    "admits": sum(a.get("admits", 0) for a in ascale),
                    "drains": sum(a.get("drains", 0) for a in ascale),
                    "shed_rate_pre": pre[0] if pre else None,
                    "shed_rate_post": post[0] if post else None,
                    "joiner_drained": res[3].get("event") == "drained",
                    "wire_frames_lost": sum(
                        d.get("wire_frames_lost", 0) for d in res),
                }
            except Exception as e:  # noqa: BLE001 - completion-gated
                grid["storm"] = {"completed": False,
                                 "error": str(e)[:300]}
        return grid

    control_grid = _control_plane_arms()

    # THE PARTITION-TOLERANCE SWEEP (this PR): (1) fence_heal — a
    # seeded symmetric link cut isolates rank 0 (the lease holder) for
    # a wall-clock window; the majority convicts it by suspicion
    # QUORUM (the minority island, suspecting everyone, convicts
    # nobody — it cannot mint a term), rank 1 takes the lease, the
    # corpse-that-isn't restores from checkpoint, and post-heal the
    # reliable layer recovers every cut frame — including the stale
    # plan the ex-holder issued INSIDE the window (--coord-plan-at),
    # which must be FENCED by term at every survivor while the
    # ex-holder itself exits fenced_out (rc 44). (2) handover — the
    # holder drains ITSELF: lease transferred (term 1 exactly once,
    # coordinator state shipped in the mbH frame), then the PR8 drain
    # path, rc 0, zero deaths.
    def _partition_arms() -> dict:
        import tempfile

        from minips_tpu import launch as _launch

        p_iters = 40 if args.quick else 80
        part_at = 8                      # cut opens at receiver clock 8
        plan_at = part_at + 2            # the ex-holder's stale plan:
        # issued at A+2, the deepest boundary its own gate (s=2) can
        # reach once the cut freezes the peers' clocks it heard at A
        base = [sys.executable, "-m",
                "minips_tpu.apps.sharded_ps_example",
                "--model", "sparse", "--mode", "ssp",
                "--staleness", "2", "--iters", str(p_iters),
                "--batch", "64", "--checkpoint-every", "4",
                # rank 0 trails (its stale plan must fire while BOTH
                # peers are already inside their cut windows) and pulls
                # only its own shard (no remote pull legs: it wedges at
                # its gate ~A+2, late enough to issue the plan)
                "--slow-rank", "0", "--slow-ms", "20",
                "--own-keys-rank", "0",
                "--coord-plan-at", str(plan_at),
                # survivors pace ~25ms/step so they are still training
                # when the window heals — the stale-plan recovery needs
                # live receivers
                "--jitter-ms", "30", "--jitter-prob", "0.8"]
        env0 = {"MINIPS_FORCE_CPU": "1", "JAX_PLATFORMS": "cpu",
                "MINIPS_RESHARD": "",
                "MINIPS_REBALANCE": "", "MINIPS_TRACE": "",
                "MINIPS_SERVE": "", "MINIPS_BUS": "",
                "MINIPS_WIRE_FMT": "", "MINIPS_CHAOS_KILL": "",
                "MINIPS_PUSH_COMM": "", "MINIPS_MESH": "",
                "MINIPS_AUTOSCALE": "", "MINIPS_OBS": "",
                "MINIPS_FLIGHT": "", "MINIPS_TENANT": "",
            "MINIPS_SLO": "", "MINIPS_TRAFFIC": ""}
        grid: dict = {"iters": p_iters}

        def rate(dones: list[dict]) -> float:
            return round(p_iters / max(max(d["wall_s"] for d in dones),
                                       1e-9), 2)

        with tempfile.TemporaryDirectory() as ck:
            try:
                rc, events = _launch.run_local_job_raw(
                    3, base + ["--checkpoint-dir", ck],
                    base_port=None,
                    env_extra={
                        **env0, "MINIPS_ELASTIC": "1",
                        # small budget + fast backoff: gaps opened
                        # against the cut exhaust INSIDE the window
                        # (give-up), so the post-heal advert must
                        # REOPEN them — the satellite path, engaged on
                        # the committed artifact
                        "MINIPS_RELIABLE":
                            "budget=4,backoff_ms=25,backoff_max_ms=150,"
                            "advert_ms=100",
                        "MINIPS_CHAOS":
                            f"5:part=1,links=0-1+0-2,at={part_at},"
                            "for=1.5s",
                        "MINIPS_HEARTBEAT":
                            "interval=0.1,timeout=0.7"},
                    timeout=300.0, kill_on_failure=False)
                by_last = {r: (ev[-1] if ev else {})
                           for r, ev in enumerate(events)}
                dones = [by_last[r] for r in (1, 2)
                         if by_last[r].get("event") == "done"]
                if len(dones) == 2:
                    mships = [d.get("membership") or {} for d in dones]
                    terms = [(m.get("lease") or {}).get("term")
                             for m in mships]
                    sums = {d.get("param_sum") for d in dones}
                    grid["fence_heal"] = {
                        "completed": True,
                        "steps_per_sec_ctrl": rate(dones),
                        "iters": p_iters,
                        "clock_min": min(d["clock"] for d in dones),
                        "lease_term": max(t for t in terms
                                          if t is not None),
                        "terms_agree": len(set(terms)) == 1,
                        # the PARTITION-FENCE evidence: stale-term
                        # frames dropped at the survivors (lease admit
                        # fence + rbP plan fence)
                        "fenced_total": sum(
                            (m.get("lease") or {}).get("fenced", 0)
                            for m in mships) + sum(
                            (d.get("rebalance") or {}).get(
                                "stale_plans_fenced", 0)
                            for d in dones),
                        "ex_coord_fenced_out":
                            by_last[0].get("event") == "fenced_out",
                        "part_dropped": sum(
                            (d.get("chaos") or {}).get(
                                "part_dropped", 0) for d in dones),
                        "reliable_reopened": sum(
                            (d.get("reliable") or {}).get(
                                "reopened", 0) for d in dones),
                        "blocks_restored": sum(
                            m.get("blocks_restored", 0)
                            for m in mships),
                        "wire_frames_lost": sum(
                            d.get("wire_frames_lost", 0)
                            for d in dones),
                        "finals_agree": len(sums) == 1,
                    }
                else:
                    grid["fence_heal"] = {
                        "completed": False,
                        "error": f"rc={rc}: {by_last}"[:400]}
            except Exception as e:  # noqa: BLE001 - completion-gated
                grid["fence_heal"] = {"completed": False,
                                      "error": str(e)[:300]}
        # -------- handover: the holder drains itself mid-run
        h_iters = 20 if args.quick else 30
        hbase = [sys.executable, "-m",
                 "minips_tpu.apps.sharded_ps_example",
                 "--model", "sparse", "--mode", "ssp",
                 "--staleness", "2", "--iters", str(h_iters),
                 "--batch", "64",
                 "--drain-rank", "0", "--drain-at", "10"]
        try:
            rc, events = _launch.run_local_job_raw(
                3, hbase, base_port=None,
                env_extra={**env0, "MINIPS_ELASTIC": "1",
                           "MINIPS_AUTOSCALE": "1",
                           "MINIPS_HEARTBEAT":
                               "interval=0.1,timeout=2.0"},
                timeout=240.0, kill_on_failure=False)
            by_last = {r: (ev[-1] if ev else {})
                       for r, ev in enumerate(events)}
            dones = [by_last[r] for r in (1, 2)
                     if by_last[r].get("event") == "done"]
            if rc == 0 and len(dones) == 2:
                mships = [d.get("membership") or {} for d in dones]
                terms = [(m.get("lease") or {}).get("term")
                         for m in mships]
                sums = {d.get("param_sum") for d in dones}
                drained = by_last[0]
                grid["handover"] = {
                    "completed": True,
                    "steps_per_sec_ctrl": round(
                        h_iters / max(max(d["wall_s"] for d in dones),
                                      1e-9), 2),
                    "lease_term": max(t for t in terms
                                      if t is not None),
                    "terms_agree": len(set(terms)) == 1,
                    "leaver_drained":
                        drained.get("event") == "drained",
                    "leaver_handovers": ((drained.get("membership")
                                          or {}).get("lease")
                                         or {}).get("handovers"),
                    "deaths": sum(m.get("deaths", 0) for m in mships),
                    "clock_min": min(d["clock"] for d in dones),
                    "iters": h_iters,
                    "wire_frames_lost": sum(
                        d.get("wire_frames_lost", 0) for d in dones),
                    "finals_agree": len(sums) == 1,
                }
            else:
                grid["handover"] = {"completed": False,
                                    "error": f"rc={rc}: {by_last}"[:400]}
        except Exception as e:  # noqa: BLE001 - completion-gated
            grid["handover"] = {"completed": False,
                                "error": str(e)[:300]}
        return grid

    partition_grid = _partition_arms()

    # THE IN-MESH COLLECTIVE DATA PLANE (this PR): the fused sweep
    # point — dense pull_all/push_dense cycles, the lrmlp weight-vector
    # shape — measured on the host wire (3 procs, zmq, ASP: its best
    # case) vs the mesh plane (one process, 3 logical ranks over 3
    # devices, push/pull as reduce-scatter/all-gather with pjit-sharded
    # table + updater state, BSP: the collective IS the barrier) vs the
    # mesh quantized tier (blk8: blockwise absmax int8 inside the
    # collective — the PR9 wire codec's second transport). Alternating
    # medians like every throughput pair. The ci/bench_regression
    # MESH-* tripwires gate: MESH-WIN — the mesh arm's rows/sec/rank
    # strictly above the wire arm's (the whole point: the data plane
    # stops paying socket+codec+frame tax and bridges toward the
    # fused-SPMD numbers); MESH-BITWISE — the BSP zmq-vs-mesh lockstep
    # drill (run in a subprocess against this tree) must report
    # bitwise-equal finals, so the transport swap provably preserves
    # the consistency contract. NOTE the rows/sec columns compare a
    # process boundary against a device mesh — integer factors by
    # design, which is the measurement (same caveat family as the
    # overlap sweep: the wire's deficit here is protocol cost).
    MESH_RANKS = 3

    def _run_mesh_arm(comm: str) -> dict:
        argv = [sys.executable, "-m",
                "minips_tpu.apps.sharded_ps_bench",
                "--path", "dense", "--plane", "mesh",
                "--mesh-ranks", str(MESH_RANKS), "--mesh-comm", comm,
                "--iters", str(iters), "--warmup", str(warmup),
                "--staleness", "0"]
        env = {**os.environ, "MINIPS_FORCE_CPU": "1",
               "JAX_PLATFORMS": "cpu", "MINIPS_MESH": ""}
        try:
            proc = subprocess.run(argv, capture_output=True, text=True,
                                  timeout=300.0, env=env)
            if proc.returncode != 0:
                raise RuntimeError(proc.stderr[-300:])
            res = json.loads([ln for ln in proc.stdout.splitlines()
                              if ln.startswith("{")][-1])
        except Exception as e:  # noqa: BLE001 - completion-gated
            return {"completed": False, "error": str(e)[:300]}
        assert res.get("plane") == "mesh" and \
            res.get("mesh_comm") == comm, res
        return {
            "completed": True,
            "plane": "mesh", "mesh_comm": comm,
            "mesh_ranks": res["mesh_ranks"],
            "device_count": res["device_count"],
            "jax_backend": res["jax_backend"],
            "rows_per_sec_per_process": res["rows_per_sec"],
            "aggregate_rows_per_sec": res["aggregate_rows_per_sec"],
            "waves": res["waves"],
            "collective_bytes_per_row_moved":
                res["collective_bytes_per_row_moved"],
        }

    # the deposit-buffer A/B (this PR): the SPARSE path at the
    # embedding shape — a big table (64Ki rows) of skinny rows where
    # each wave touches a few hundred keys. The dense deposit stages a
    # full [rows, dim] host buffer per logical rank regardless; the
    # sparse deposit stages COO streams and densifies via segment-sum
    # scatter ON DEVICE, so peak host bytes scale with TOUCHED rows.
    # MESH-SPARSE gates: >= 4x peak-byte reduction, throughput no
    # worse (same collective — the exchange is untouched, only the
    # staging layout changes)
    def _run_mesh_deposit_arm(dep: str) -> dict:
        argv = [sys.executable, "-m",
                "minips_tpu.apps.sharded_ps_bench",
                "--path", "sparse", "--plane", "mesh",
                "--mesh-ranks", "2", "--mesh-comm", "float32",
                "--mesh-deposit", dep,
                "--rows", str(1 << 16), "--dim", "8", "--batch", "64",
                "--iters", str(iters), "--warmup", str(warmup),
                "--staleness", "0"]
        env = {**os.environ, "MINIPS_FORCE_CPU": "1",
               "JAX_PLATFORMS": "cpu", "MINIPS_MESH": "",
               "MINIPS_MESH_SPARSE": ""}
        try:
            proc = subprocess.run(argv, capture_output=True, text=True,
                                  timeout=300.0, env=env)
            if proc.returncode != 0:
                raise RuntimeError(proc.stderr[-300:])
            res = json.loads([ln for ln in proc.stdout.splitlines()
                              if ln.startswith("{")][-1])
        except Exception as e:  # noqa: BLE001 - completion-gated
            return {"completed": False, "error": str(e)[:300]}
        assert res.get("deposit") == dep, res
        return {
            "completed": True, "deposit": dep,
            "rows_per_sec_per_process": res["rows_per_sec"],
            "peak_deposit_bytes": res["peak_deposit_bytes"],
            "sparse_waves": res["sparse_waves"],
            "collective_bytes_per_row_moved":
                res["collective_bytes_per_row_moved"],
        }

    def _mesh_sparse_arms(reps: int) -> dict:
        runs: dict[str, list[dict]] = {"dense": [], "sparse": []}
        for _ in range(reps):  # alternating pairs, like every A/B
            runs["dense"].append(_run_mesh_deposit_arm("dense"))
            runs["sparse"].append(_run_mesh_deposit_arm("sparse"))

        def med(a: str) -> dict:
            ok = [r for r in runs[a] if r.get("completed")]
            if not ok:
                return runs[a][-1]
            by = sorted(ok,
                        key=lambda r: r["rows_per_sec_per_process"])
            return {**by[len(by) // 2], "reps": reps}

        g = {"workload": {"path": "sparse", "rows": 1 << 16,
                          "dim": 8, "batch": 64, "mesh_ranks": 2,
                          "mesh_comm": "float32"},
             "dense": med("dense"), "sparse": med("sparse")}
        dn, sp = g["dense"], g["sparse"]
        if dn.get("completed") and sp.get("completed"):
            g["peak_bytes_ratio"] = round(
                dn["peak_deposit_bytes"]
                / max(sp["peak_deposit_bytes"], 1), 3)
            g["rows_ratio"] = round(
                sp["rows_per_sec_per_process"]
                / max(dn["rows_per_sec_per_process"], 1e-9), 3)
        return g

    def _mesh_arms(reps: int) -> dict:
        arms = {"wire": lambda: {
                    **_run(3, "dense", iters, warmup, "zmq"),
                    "plane": "wire"},
                "mesh": lambda: _run_mesh_arm("float32"),
                "mesh_blk8": lambda: _run_mesh_arm("blk8")}
        runs: dict[str, list[dict]] = {a: [] for a in arms}
        for _ in range(reps):
            for a, fn in arms.items():
                runs[a].append(fn())

        def med(arm: str) -> dict:
            ok = [r for r in runs[arm] if r.get("completed")]
            if not ok:
                return runs[arm][-1]
            by = sorted(ok, key=lambda r: r["rows_per_sec_per_process"])
            return {**by[len(by) // 2], "reps": reps}
        grid = {a: med(a) for a in arms}
        # MESH-BITWISE: the zmq-vs-mesh BSP lockstep drill, run from the
        # repo root (it drives the tests/ harness) in a subprocess so
        # the driver never initializes a jax backend itself
        drill_argv = [sys.executable, "-m",
                      "minips_tpu.apps.sharded_ps_bench",
                      "--mesh-bitwise-drill"]
        try:
            proc = subprocess.run(
                drill_argv, capture_output=True, text=True,
                timeout=300.0,
                cwd=os.path.dirname(os.path.abspath(__file__)),
                env={**os.environ, "MINIPS_FORCE_CPU": "1",
                     "JAX_PLATFORMS": "cpu", "MINIPS_MESH": ""})
            res = json.loads([ln for ln in proc.stdout.splitlines()
                              if ln.startswith("{")][-1])
            grid["bitwise"] = {"equal": bool(res.get("bitwise_equal")),
                               "rows_checked":
                                   int(res.get("rows_checked", 0))}
            if res.get("error"):
                grid["bitwise"]["error"] = res["error"]
        except Exception as e:  # noqa: BLE001 - the gate reads this
            grid["bitwise"] = {"equal": False, "rows_checked": 0,
                               "error": str(e)[:300]}
        grid["sparse_deposit"] = _mesh_sparse_arms(reps)
        return grid

    mesh_grid = _mesh_arms(o_reps)

    # THE FAIL-SLOW SWEEP (this PR): a seeded slow# link tax makes
    # rank 1 (the storm range's owner) slow-but-alive — its beats
    # land, nothing dies, every read to it rides the tax. Three arms +
    # the armed-idle bitwise stamp: (1) unmitigated — the gray failure
    # as the pre-this-PR fleet lives it (reads pay the tail, steps
    # complete); (2) hedged — serve-plane replicas + MINIPS_HEDGE:
    # rank 0 (the designated reader: NOT a holder — rank 2 holds the
    # sick rank's replicas and serves itself locally) must land its
    # warmed windowed read p99 STRICTLY below the unmitigated arm's
    # (SLOW-HEDGE); (3) demote — + MINIPS_SLOW detection, quorum slow
    # verdict over heartbeat ballots, and the rebalancer's demote pass
    # migrating the sick rank's hot blocks off it (SLOW-DRAIN: >= 1
    # block out of rank 1, zero lost steps, bitwise survivors, the
    # four flight events in the post-mortem boxes). SLOW-IDLE rides
    # the --fail-slow-idle-drill lockstep stamp.
    fail_slow_grid = fail_slow_arms(quick=args.quick)

    reshard_grid = reshard_arms(quick=args.quick)

    # THE HIER SWEEP (this PR): the two-level push tree vs the flat
    # per-worker wire on the same seeded zipf-overlap workload —
    # HIER-WIN wants the tree's cross-host leader leg >= 1.7x fewer
    # bytes with matching loss; the bitwise/idle drills pin exactness
    hier_grid = hier_arms(quick=args.quick)

    # THE HYBRID SWEEP (this PR): the tree's leader reduce moved onto
    # the in-host device mesh — HYBRID-WIN wants the hybrid arm
    # strictly faster than the host-agg tree at matching loss with
    # cross-host bytes no worse; HYBRID-IDLE and the one-device
    # degenerate drill pin exactness
    hybrid_grid = hybrid_arms(quick=args.quick)

    # THE MULTI-TENANT SWEEP (this PR): a training tenant next to a
    # storming zipf inference tenant in ONE job — TENANT-ISO wants the
    # isolated arm's trn throughput within 10% of its solo arm with
    # inf shedding into its OWN budget (trn's attributed counters
    # zero, the shared-bucket contrast arm visibly coupled);
    # TENANT-IDLE wants the bare-default-tenant lockstep bitwise
    tenant_grid = tenant_arms(quick=args.quick)

    # THE MILLION-USER SWEEP (this PR): an open-loop zipf traffic
    # driver on a fixed arrival schedule against pull_serving while
    # training runs — TRAFFIC-FRESH wants the flash crowd degrading to
    # latency (zero stale reads, bounded freshness p99, replica budget
    # provably flexed above its configured count); TRAFFIC-SHED wants
    # overload shedding into the inf tenant's own budget with an
    # slo_burn flight event; TRAFFIC-IDLE wants the rate-0 armed
    # driver bitwise-identical to off with zero requests scheduled
    traffic_grid = traffic_arms(quick=args.quick)

    # resolved JAX backend stamp (satellite): probed in a SUBPROCESS so
    # the driver never grabs the TPU out from under a worker (libtpu is
    # exclusive per process) — ci/bench_regression.py refuses to
    # compare artifacts whose backends differ (a CPU record is silently
    # incomparable to a TPU one)
    def _resolve_jax_backend() -> str:
        try:
            probe = subprocess.run(
                [sys.executable, "-c",
                 "import jax, sys; sys.stdout.write("
                 "jax.default_backend())"],
                capture_output=True, text=True, timeout=120.0,
                env={**os.environ, "JAX_PLATFORMS": os.environ.get(
                    "JAX_PLATFORMS", "")})
            out = (probe.stdout or "").strip().splitlines()
            return out[-1] if probe.returncode == 0 and out \
                else "unknown"
        except Exception:  # noqa: BLE001 - a stamp, not a gate
            return "unknown"

    # resolved mesh/device SHAPE stamp (satellite): backend:device-count
    # as the mesh arms saw it — ci/bench_regression.py refuses to
    # compare artifacts across shapes the way it refuses cross-backend
    # pairs (a mesh point at 8 devices is incomparable to one at 3; the
    # collective cost scales with the ring)
    def _resolve_device_shape() -> str:
        shape = (mesh_grid.get("mesh") or {})
        if shape.get("completed"):
            return f"{shape['jax_backend']}:{shape['device_count']}"
        return "unknown"

    headline = curve["3"]["rows_per_sec_per_process"]
    print(json.dumps({
        "metric": "sharded-PS rows/sec/process (sparse pull+push, "
                  "3 procs, zmq, CPU loopback control plane)",
        "value": headline,
        "unit": "rows/sec/process",
        "vs_baseline": None,  # control-plane rate; not a chip number
        "device": "cpu-loopback",
        # the resolved JAX platform these numbers were measured under:
        # the regression gate refuses cross-backend comparisons
        "jax_backend": _resolve_jax_backend(),
        # the mesh/device shape the collective-plane arms ran at
        # (backend:device-count) — the gate refuses cross-shape
        # comparisons the same way
        "device_shape": _resolve_device_shape(),
        "scaling_sparse_zmq": curve,
        "bus_comparison_3proc": buses,
        "transport_comparison_3proc": transport_grid,
        "path_comparison_3proc": paths,
        "push_wire_comparison_3proc": wires,
        "pull_wire_comparison_3proc": pull_wires,
        "overlap_on_off_3proc": over,
        "overlap_on_off_fit": {"nprocs": n_fit, **over_fit},
        "cache_comparison_3proc": cache_grid,
        "wire_compression_3proc": wire_comp_grid,
        "chaos_resilience_3proc": chaos_grid,
        "rebalance_3proc": rebalance_grid,
        "trace_overhead_3proc": trace_grid,
        "obs_tax_3proc": obs_tax_grid,
        "pull_storm_3proc": storm_grid,
        "elastic_membership_3proc": elastic_grid,
        "control_plane_3proc": control_grid,
        "partition_3proc": partition_grid,
        "fail_slow_3proc": fail_slow_grid,
        "reshard_3proc": reshard_grid,
        "hier_agg_3proc": hier_grid,
        "hybrid_agg_3proc": hybrid_grid,
        "multi_tenant_3proc": tenant_grid,
        "million_user_3proc": traffic_grid,
        "mesh_plane_fused": mesh_grid,
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
