"""CI bench-regression gate for the sharded-PS artifact.

Compares every sweep point of a NEW ``BENCH_SHARDED_PS.json`` against a
PRIOR artifact and fails (exit 1) when any throughput point regresses by
more than ``--tolerance`` (default 10%). Points are matched by their full
path inside the artifact (e.g. ``scaling_sparse_zmq/3`` or
``overlap_on_off_3proc/on``), so a sweep added in the new artifact never
fails the gate (there is no prior point to regress from) — but a sweep
point that DISAPPEARS does fail it: silently dropping a measurement is
how a regression hides.

The compared metric is ``rows_per_sec_per_process`` — the per-point
throughput every sweep reports. Wire-bytes numbers are deliberately NOT
gated on direction (a codec change moves them on purpose); they are
printed for the reviewer instead.

Absolute (prior-free) gates ride along: ``cache_tripwires`` fails a
new artifact whose ``cache_comparison_3proc`` zipf arms report a zero
hit rate with the cache on and staleness >= 1 — the "cache silently
disabled" failure mode, which a pure throughput comparison can miss —
and ``chaos_tripwires`` guards the ``chaos_resilience_3proc`` sweep:
the drop-0 chaos arm must stay within slack of the clean arm (the
reliable layer may not tax the lossless path) and every drop>0
retransmit-on arm must have completed with zero unrecovered frames
(seeded loss must degrade to latency, never to death).
``transport_tripwires`` (TRANSPORT-WIN/TRANSPORT-COMPOSE) guards the
``transport_comparison_3proc`` sweep: the shm-ring arm must beat the
seed zmq-JSON arm on rows/sec with bytes/row unchanged, and the seeded
chaos+reliable arm on the shm backend must complete with zero
unrecovered frames (the fault layers must stack on the new transport).
``wire_compression_tripwires`` (WIRE-BYTES/WIRE-CONVERGE) guards the
``wire_compression_3proc`` sweep: the sparse top-k push wire must beat
the int8 wire's push bytes/row by >= 2x on zipf with zero residual
mass stranded, and the error-feedback convergence drill must pin the
loss trajectory to the dense wire within tolerance.
``rebalance_tripwires`` (REBAL-SKEW/REBAL-DEAD) guards the
``rebalance_3proc`` sweep: the unpermuted-zipf rebalancer-on arm must
complete with >= 1 migration and max/mean per-shard serve load
strictly below the static arm's — skewed-arm rows/sec stay
gate-invisible (``rows_per_sec_skewed``) like the chaos arms'.
``trace_tripwires`` (TRACE-TAX/TRACE-MERGE) guards the
``trace_overhead_3proc`` sweep: the MINIPS_TRACE-armed arm must stay
within 15% of the untraced arm AND its per-rank traces must merge
(merge CLI exit 0, >= 1 cross-rank flow). ``obs_tripwires``
(OBS-TAX/FLIGHT-DUMP) guards the always-on observability layer: the
default arm (windowed metrics + flight recorder on) must stay within
the TRACE-TAX-style band of a ``MINIPS_OBS=0 MINIPS_FLIGHT=0`` build
on the ``obs_tax_3proc`` point, and the control-plane kill arm must
leave >= 1 valid flight dump per survivor with the flight merge CLI
exiting 0 — the zero-pre-arming post-mortem claim, gated per artifact.
``serve_tripwires``
(SERVE-SLO/SERVE-STALE/SERVE-SHED) guards the ``pull_storm_3proc``
sweep: the replicas-on arm must beat the off arm on read rows/sec and
median latency with replicas actually engaged (p99 inside a slack
band — the tail is scheduler noise on the CI container), zero reads
may violate the staleness bound, and the admission-throttled arm must
complete via explicit refusal, never a timeout poison.
``elastic_tripwires`` (ELASTIC-DEAD/ELASTIC-JOIN) guards the
``elastic_membership_3proc`` sweep: the seeded-SIGKILL arm's
survivors must complete with >= 1 range restored from the elastic
checkpoint, zero unrecovered frames, finite loss and bitwise-agreeing
finals, and the standby-admission arm must complete with the joiner
serving > 0 rows.
``control_plane_tripwires`` (CTRL-FAILOVER/CTRL-SCALE) guards the
``control_plane_3proc`` sweep: the coordinator-kill arm's survivors
must complete the full step count with the lease advanced exactly
once, >= 1 range restored, zero unrecovered frames and bitwise
agreement; the storm-autoscale arm must complete with >= 1 autoscaler
admit and >= 1 drain and the post-admit shed rate at or below the
pre-admit rate; the steady armed-idle arm must complete with zero
membership changes. Rates ride gate-invisible keys
(``steps_per_sec_ctrl``) like every chaos arm.
``partition_tripwires`` (PARTITION-FENCE/PARTITION-HEAL/HANDOVER)
guards the ``partition_3proc`` sweep: the link-cut arm's minority
ex-coordinator must exit fenced_out with its recovered stale-term
plan dropped (fenced) at the survivors, who must complete every step
at term 1 exactly with zero unrecovered frames, bitwise agreement,
and the injector provably engaged (part_dropped > 0); the
holder-self-drain arm must complete with the term advanced exactly
once, zero deaths, the leaver exiting rc 0 via the drain path, and
bitwise agreement.
``fail_slow_tripwires`` (SLOW-HEDGE/SLOW-DRAIN/SLOW-IDLE) guards the
``fail_slow_3proc`` sweep: under a seeded ``slow#`` link tax on one
rank, the hedged arm's designated reader must land its warmed windowed
read p99 STRICTLY below the unmitigated arm's with >= 1 hedge actually
fired and the injector provably engaged; the demote arm must complete
every step with >= 1 quorum slow verdict, >= 1 hot block migrated off
the sick rank, zero unrecovered frames, bitwise survivors, and the
four fail-slow flight events (slow_suspect/slow_verdict/hedge_fired/
demote) present in the post-mortem boxes; the armed-idle lockstep
drill must report bitwise-equal finals. Rates ride gate-invisible
keys (``steps_per_sec_slow``).
``hier_tripwires`` (HIER-WIN/HIER-IDLE) guards the ``hier_agg_3proc``
sweep: the two-level push tree's arm must complete the same seeded
zipf-overlap workload as the accounting-only flat arm with the tree
provably engaged (aggregate frames + contributions, zero fallbacks),
its cross-host leader-leg bytes >= 1.7x below the flat arm's, the
loss trajectories matching, and both bitwise drills green — the
compression-off tree equal to the flat wire bit-for-bit (with
aggregation provably ON in the stamp), and armed-idle (group=1)
equal to off bit-for-bit with zero aggregate frames.
``mesh_tripwires`` (MESH-WIN/MESH-BITWISE) guards the
``mesh_plane_fused`` sweep: the in-mesh collective plane's arm must
beat the host-wire arm on rows/sec strictly (the data plane exists to
stop paying socket+codec tax), the quantized blk8 arm must complete,
and the BSP zmq-vs-mesh lockstep drill must report bitwise-equal
finals (the transport swap may not move one bit of training state).
Artifacts also carry a resolved ``jax_backend`` stamp, and the gate
REFUSES to compare artifacts across backends (cross-backend rates
differ by integer factors; re-base instead) — and likewise a
``device_shape`` stamp (backend:device-count of the mesh arms), with
cross-SHAPE comparisons refused the same way (collective cost scales
with the ring).

Usage:
    python ci/bench_regression.py PRIOR.json NEW.json [--tolerance 0.10]
    python ci/bench_regression.py --against-git [NEW.json]
        (prior = `git show HEAD:BENCH_SHARDED_PS.json`)

These loopback control-plane rates wobble run-to-run on a shared CI
host; 10% is the observed noise ceiling of the 3-proc points with the
default --iters 60. Tighten only with pinned cores.

The gate is only meaningful when prior and new were measured on the
SAME host class: absolute loopback rates swing integer factors across
machines (the artifact's own header says these are never chip rates).
Re-measuring on different hardware REQUIRES re-basing — commit the
fresh artifact alongside the change and say so; the gate then guards
every same-host run against that new baseline.
"""

from __future__ import annotations

import argparse
import json
import subprocess
import sys

METRIC = "rows_per_sec_per_process"


def throughput_points(artifact: dict) -> dict[str, float]:
    """Flatten ``{path: rows_per_sec_per_process}`` over every sweep
    point in the artifact, path-keyed so prior/new match positionally."""
    out: dict[str, float] = {}

    def walk(node, path):
        if not isinstance(node, dict):
            return
        if METRIC in node:
            out[path] = float(node[METRIC])
        for k, v in node.items():
            walk(v, f"{path}/{k}" if path else str(k))

    walk(artifact, "")
    return out


def cache_tripwires(new: dict) -> list[str]:
    """The 'cache silently disabled' tripwire: in the
    ``cache_comparison_3proc`` sweep, the zipf arms with staleness >= 1
    and the cache ON must show a hit rate strictly above 0 — a zipfian
    batch re-draws hot rows every step, and with SSP slack the cache
    serving NONE of them means the lever quietly fell off (flag
    plumbing, stamp regression, over-eager invalidation) while
    rows/sec alone might still look fine. s=0 (BSP) arms are exempt:
    a stamp can never satisfy the next clock's bound there, so ~0 is
    the CORRECT hit rate. Arms missing entirely are the generic
    MISSING check's job (dropped sweep points fail there)."""
    problems = []
    zipf = (new.get("cache_comparison_3proc") or {}).get("zipf") or {}
    for sname, arms in sorted(zipf.items()):
        try:
            s = int(sname.lstrip("s"))
        except ValueError:
            continue
        on = (arms or {}).get("on") or {}
        hr = on.get("cache_hit_rate")
        if s >= 1 and not (isinstance(hr, (int, float)) and hr > 0):
            problems.append(
                f"CACHE-DEAD cache_comparison_3proc/zipf/{sname}/on: "
                f"hit-rate {hr!r} with staleness {s} — the client row "
                "cache is silently disabled")
    return problems


CHAOS_TAX_TOLERANCE = 0.25  # drop-0 chaos arm vs clean arm slack. On a
# CPU-saturated loopback host every per-frame instruction and every
# extra thread wake shows up directly in rows/sec (the overlap/cache
# sweeps carry the same caveat): the committed baseline already carries
# a ~12% median tax (218.3k vs 247.4k), inside a drift band whose
# single runs have crowned either arm by 2x — so the trip point sits at
# 0.75, leaving real headroom over the baseline while still catching
# the failure classes this gate exists for (a sleep on the hot path, a
# per-frame sync round trip — those cost integer factors, not percent).


def chaos_tripwires(new: dict) -> list[str]:
    """Absolute (prior-free) gates on the ``chaos_resilience_3proc``
    sweep; vacuous when the sweep is absent (other benches).

    - CHAOS-TAX: the drop=0 chaos arm (injector armed, zero rates,
      retransmit on) must stay within ``CHAOS_TAX_TOLERANCE`` of the
      clean arm — the delivery layer may not tax the lossless path.
    - CHAOS-DEAD: every drop>0 retransmit-ON arm must have COMPLETED
      with rows/sec > 0 and zero unrecovered frames — the whole point
      of the layer is that seeded loss degrades to latency, not death
      (the retransmit-off twins are *expected* to die and are recorded,
      not gated)."""
    grid = new.get("chaos_resilience_3proc") or {}
    if not grid:
        return []
    problems = []
    clean = (grid.get("clean") or {}).get(METRIC)
    d0 = (grid.get("drop0_on") or {}).get(METRIC)
    if isinstance(clean, (int, float)) and clean > 0:
        if not isinstance(d0, (int, float)) or \
                d0 / clean < 1.0 - CHAOS_TAX_TOLERANCE:
            problems.append(
                f"CHAOS-TAX chaos_resilience_3proc/drop0_on: "
                f"{d0!r} vs clean {clean:.1f} rows/s/proc — the "
                f"reliable layer is taxing the lossless path beyond "
                f"{CHAOS_TAX_TOLERANCE * 100:.0f}%")
    for arm in ("drop1_on", "drop5_on"):
        a = grid.get(arm) or {}
        # lossy arms keep their rate under a gate-invisible key: they
        # are absolute completion gates, never run-to-run comparisons
        rate = a.get("rows_per_sec_lossy", a.get(METRIC))
        if not a.get("completed") or \
                not (isinstance(rate, (int, float)) and rate > 0):
            problems.append(
                f"CHAOS-DEAD chaos_resilience_3proc/{arm}: rate "
                f"{rate!r} completed={a.get('completed')!r} — seeded "
                "loss with retransmit on must complete (loss should "
                "degrade to latency, not death)")
        elif a.get("wire_frames_lost", 0):
            problems.append(
                f"CHAOS-LEAK chaos_resilience_3proc/{arm}: "
                f"{a['wire_frames_lost']} unrecovered frames with the "
                "retransmit layer on — recovery is silently failing")
    return problems


TRANSPORT_BYTES_SLACK = 0.02  # bytes/row must match across transport
# arms: framing moves HEAD bytes, never blob bytes, and bytes/row-moved
# is computed from the table-level blob counters — a divergence means a
# codec started re-encoding (or dropping) payload rows.


def transport_tripwires(new: dict) -> list[str]:
    """Absolute (prior-free) gates on the ``transport_comparison_3proc``
    sweep; vacuous when the sweep is absent (other benches).

    - TRANSPORT-WIN: the shm-ring arm must beat the seed zmq-JSON arm
      on rows/sec STRICTLY (alternating medians — the whole point of
      the transport is that loopback benches stop paying codec+socket
      tax), with bytes/row-moved unchanged across arms (framing must
      never touch blob bytes).
    - TRANSPORT-COMPOSE: the seeded chaos(drop>=1%)+reliable arm ON THE
      SHM BACKEND must have completed with zero unrecovered frames and
      the counters proving both layers engaged — the chaos/reliable
      stack wraps the bus, so a new backend that quietly bypasses it
      would still post a fast number while losing its fault story."""
    grid = new.get("transport_comparison_3proc") or {}
    if not grid:
        return []
    problems = []
    zj = (grid.get("zmq_json") or {}).get(METRIC)
    shm = (grid.get("shm") or {}).get(METRIC)
    if not (isinstance(zj, (int, float)) and isinstance(shm, (int, float))
            and shm > zj):
        problems.append(
            f"TRANSPORT-WIN transport_comparison_3proc: shm arm "
            f"{shm!r} rows/s/proc is not strictly above zmq-json "
            f"{zj!r} — the ring transport is not beating the seed "
            "wire on loopback")
    bj = (grid.get("zmq_json") or {}).get("wire_bytes_per_row_moved")
    for arm in ("zmq_bin", "shm"):
        ba = (grid.get(arm) or {}).get("wire_bytes_per_row_moved")
        if isinstance(bj, (int, float)) and isinstance(ba, (int, float)) \
                and bj > 0 and abs(ba - bj) / bj > TRANSPORT_BYTES_SLACK:
            problems.append(
                f"TRANSPORT-WIN transport_comparison_3proc/{arm}: "
                f"bytes/row {ba} vs zmq-json {bj} — framing changed "
                "payload bytes, not just head bytes")
    comp = grid.get("shm_compose") or {}
    rate = comp.get("rows_per_sec_lossy")
    if not comp.get("completed") or \
            not (isinstance(rate, (int, float)) and rate > 0):
        problems.append(
            f"TRANSPORT-COMPOSE transport_comparison_3proc/shm_compose: "
            f"rate {rate!r} completed={comp.get('completed')!r} — "
            "seeded chaos+reliable on the shm backend must complete "
            "(loss should degrade to latency on every transport)")
    elif comp.get("wire_frames_lost", 0):
        problems.append(
            f"TRANSPORT-COMPOSE transport_comparison_3proc/shm_compose: "
            f"{comp['wire_frames_lost']} unrecovered frames — recovery "
            "is silently failing on the shm backend")
    elif not comp.get("chaos_dropped") or not comp.get("retransmits_got"):
        problems.append(
            f"TRANSPORT-COMPOSE transport_comparison_3proc/shm_compose: "
            f"chaos_dropped={comp.get('chaos_dropped')!r} "
            f"retransmits_got={comp.get('retransmits_got')!r} — the "
            "drill proved nothing (injector or repair never engaged)")
    return problems


WIRE_BYTES_FACTOR = 2.0  # topk8 push bytes/row must beat int8 by this
# factor on the zipf hot-set arm — the integer-factor lever the sparse
# index+code wire exists for (selection ships the mass, error feedback
# repays the remainder compressed-or-aged, so paying MORE than half the
# int8 wire means selection or the residual fold silently fell off).

WIRE_CONVERGE_SLACK = 1.3  # topk8 final loss vs the dense wire's, plus
# a small absolute epsilon: error feedback provably repays withheld
# mass within the staleness bound, so the trajectories track within
# run-to-run noise — a blowout here means residuals are stranded or
# double-folded, which rows/sec alone would never catch.


def wire_compression_tripwires(new: dict) -> list[str]:
    """Absolute (prior-free) gates on the ``wire_compression_3proc``
    sweep (the sparse top-k + error-feedback push wire); vacuous when
    the sweep is absent (other benches).

    - WIRE-BYTES: the topk8 arm's PUSH bytes/row-moved must beat the
      int8 arm's by >= ``WIRE_BYTES_FACTOR`` on the zipf workload,
      with the arm completed, zero unrecovered frames, and zero
      resident residual rows at exit (mass conservation is part of the
      byte claim: a wire that 'saves' bytes by stranding gradient is
      lying).
    - WIRE-CONVERGE: the convergence drill (sparse LR at SSP(1), f32
      vs topk8 + error feedback) must complete on both arms with the
      topk8 final loss finite and within ``WIRE_CONVERGE_SLACK`` of
      the dense wire's, survivors' finals bitwise-agreeing, and no
      residual mass resident after finalize."""
    grid = new.get("wire_compression_3proc") or {}
    if not grid:
        return []
    problems = []
    for arm in ("topk8", "topk4"):
        a = grid.get(arm) or {}
        if not a.get("completed"):
            problems.append(
                f"WIRE-BYTES wire_compression_3proc/{arm}: completed="
                f"{a.get('completed')!r} — the compressed-push arm "
                "must complete")
        elif a.get("wire_frames_lost", 0):
            problems.append(
                f"WIRE-BYTES wire_compression_3proc/{arm}: "
                f"{a['wire_frames_lost']} unrecovered frames")
        elif a.get("ef_resident_rows"):
            problems.append(
                f"WIRE-BYTES wire_compression_3proc/{arm}: "
                f"{a['ef_resident_rows']} residual rows resident after "
                "finalize — error-feedback mass was stranded")
    bi = (grid.get("int8") or {}).get("wire_push_bytes_per_row_moved")
    bt = (grid.get("topk8") or {}).get("wire_push_bytes_per_row_moved")
    if not (isinstance(bi, (int, float)) and isinstance(bt, (int, float))
            and bi > 0 and bt <= bi / WIRE_BYTES_FACTOR):
        problems.append(
            f"WIRE-BYTES wire_compression_3proc: topk8 push "
            f"bytes/row {bt!r} does not beat int8's {bi!r} by "
            f">= {WIRE_BYTES_FACTOR}x on zipf — the sparse wire's "
            "selection or residual fold is silently disabled")
    conv = grid.get("converge") or {}
    f32 = conv.get("f32") or {}
    tk8 = conv.get("topk8") or {}
    if not (f32.get("completed") and tk8.get("completed")):
        problems.append(
            f"WIRE-CONVERGE wire_compression_3proc/converge: f32 "
            f"completed={f32.get('completed')!r} topk8 completed="
            f"{tk8.get('completed')!r} — the drill arms must complete")
        return problems
    lf, lt = f32.get("loss_last"), tk8.get("loss_last")
    finite = (isinstance(lt, (int, float)) and lt == lt
              and abs(lt) != float("inf"))
    if not finite or not isinstance(lf, (int, float)) \
            or lt > lf * WIRE_CONVERGE_SLACK + 0.02:
        problems.append(
            f"WIRE-CONVERGE wire_compression_3proc/converge: topk8 "
            f"loss {lt!r} vs dense {lf!r} (slack "
            f"{WIRE_CONVERGE_SLACK}x) — error feedback is not "
            "preserving the loss trajectory")
    if not tk8.get("finals_agree"):
        problems.append(
            "WIRE-CONVERGE wire_compression_3proc/converge: topk8 "
            "finals disagree across ranks — the residual flush left "
            "replicas torn")
    if tk8.get("ef_resident_rows"):
        problems.append(
            f"WIRE-CONVERGE wire_compression_3proc/converge: "
            f"{tk8['ef_resident_rows']} residual rows resident after "
            "finalize — mass stranded")
    return problems


def rebalance_tripwires(new: dict) -> list[str]:
    """Absolute (prior-free) gates on the ``rebalance_3proc`` sweep;
    vacuous when the sweep is absent (other benches).

    - REBAL-SKEW: the unpermuted-zipf arm with the rebalancer ON must
      end with max/mean per-shard serve load STRICTLY below the static
      arm's, having performed >= 1 migration — otherwise the subsystem
      is silently disabled (env plumbing, heat dead, planner never
      firing) while the run still completes.
    - REBAL-DEAD: the rebalance arm must COMPLETE with zero unrecovered
      frames (migration must never convert skew into poisons). Skewed
      arms' rows/sec live under a gate-invisible key
      (``rows_per_sec_skewed``) like the chaos arms — one hot owner's
      serve rate must never feed the run-to-run ±10% gate."""
    grid = new.get("rebalance_3proc") or {}
    if not grid:
        return []
    problems = []
    static = grid.get("static") or {}
    rb = grid.get("rebalance") or {}
    if not rb.get("completed") or rb.get("wire_frames_lost", 0):
        problems.append(
            f"REBAL-DEAD rebalance_3proc/rebalance: completed="
            f"{rb.get('completed')!r} frames_lost="
            f"{rb.get('wire_frames_lost')!r} — the rebalancer arm must "
            "complete cleanly")
        return problems
    if not rb.get("migrations"):
        problems.append(
            "REBAL-SKEW rebalance_3proc/rebalance: 0 migrations on "
            "unpermuted zipf — the rebalancer is silently disabled")
    si = static.get("serve_load_imbalance")
    ri = rb.get("serve_load_imbalance")
    if not (isinstance(si, (int, float)) and isinstance(ri, (int, float))
            and ri < si):
        problems.append(
            f"REBAL-SKEW rebalance_3proc: serve-load imbalance "
            f"{ri!r} (rebalance) is not strictly below {si!r} (static) "
            "— migration is not flattening the hot shard")
    return problems


TRACE_TAX_TOLERANCE = 0.15  # traced arm vs untraced arm slack. The
# tracer's on-path cost is one monotonic() call + a tuple + a deque
# append per event; on the CPU-saturated loopback host that books as a
# few percent. The failure classes this gate exists for — an event
# formatter on the hot path, an unbounded ring growing into swap, a
# lock on the record path — cost integer factors, not percent.


def trace_tripwires(new: dict) -> list[str]:
    """Absolute (prior-free) gates on the ``trace_overhead_3proc``
    sweep; vacuous when the sweep is absent (other benches).

    - TRACE-TAX: the MINIPS_TRACE-armed arm must stay within
      ``TRACE_TAX_TOLERANCE`` of the untraced arm (alternating-median,
      same honesty rules as CHAOS-TAX) — observability may not tax the
      wire it observes.
    - TRACE-MERGE: the traced arm must have produced traces the merge
      CLI combined (exit 0) with >= 1 cross-rank flow — a trace that
      exists but no longer links client pulls to owner serves is the
      'silently disabled' failure mode of this layer."""
    grid = new.get("trace_overhead_3proc") or {}
    if not grid:
        return []
    problems = []
    un = (grid.get("untraced") or {}).get(METRIC)
    tr = grid.get("traced") or {}
    rate = tr.get(METRIC)
    if isinstance(un, (int, float)) and un > 0:
        if not isinstance(rate, (int, float)) or \
                rate / un < 1.0 - TRACE_TAX_TOLERANCE:
            problems.append(
                f"TRACE-TAX trace_overhead_3proc/traced: {rate!r} vs "
                f"untraced {un:.1f} rows/s/proc — tracing is taxing "
                f"the wire beyond {TRACE_TAX_TOLERANCE * 100:.0f}%")
    if not tr.get("merge_ok") or not tr.get("flows_linked"):
        problems.append(
            f"TRACE-MERGE trace_overhead_3proc/traced: merge_ok="
            f"{tr.get('merge_ok')!r} flows_linked="
            f"{tr.get('flows_linked')!r} — the traced arm must emit a "
            "merge-able trace with >= 1 cross-rank flow")
    return problems


OBS_TAX_TOLERANCE = 0.15  # always-on windowed layer + flight ring vs a
# build with both disabled — the TRACE-TAX band: the on-path cost is one
# snapshot pass per CLOCK BOUNDARY (window roll) plus branch-guarded
# ring appends at decision sites, nothing per frame. The failure classes
# this catches — a roll on the frame path, an unbounded ring, dump I/O
# on a hot path — cost integer factors, not percent.

FLIGHT_SURVIVORS = 2  # the control-plane kill arm's surviving ranks


def obs_tripwires(new: dict) -> list[str]:
    """Absolute (prior-free) gates on the always-on observability layer
    (this PR); vacuous when the inputs are absent (other benches, or an
    artifact measured before the layer existed).

    - OBS-TAX: the DEFAULT arm (windowed layer + flight recorder on)
      must stay within ``OBS_TAX_TOLERANCE`` of the
      ``MINIPS_OBS=0 MINIPS_FLIGHT=0`` arm on the 3-proc point
      (alternating-median, the TRACE-TAX honesty rules) — an always-on
      layer that taxes the wire would be a regression every production
      run pays.
    - FLIGHT-DUMP: the control-plane kill arm must leave >= 1 valid
      flight dump PER SURVIVOR with the merge CLI exiting 0 — zero
      dumps means the black box silently fell off exactly where it
      exists to testify. Keyed on the arm carrying the flight fields
      (an older bench's artifact is not judged for a gate its code
      predates; a NEW bench that collected zero dumps records 0 and
      trips)."""
    problems = []
    grid = new.get("obs_tax_3proc") or {}
    if grid:
        off = (grid.get("obs_off") or {}).get(METRIC)
        on = grid.get("obs_on") or {}
        rate = on.get(METRIC)
        if isinstance(off, (int, float)) and off > 0:
            if not isinstance(rate, (int, float)) or \
                    rate / off < 1.0 - OBS_TAX_TOLERANCE:
                problems.append(
                    f"OBS-TAX obs_tax_3proc/obs_on: {rate!r} vs "
                    f"obs_off {off:.1f} rows/s/proc — the always-on "
                    f"windowed+flight layer is taxing the wire beyond "
                    f"{OBS_TAX_TOLERANCE * 100:.0f}%")
        else:
            problems.append(
                f"OBS-TAX obs_tax_3proc/obs_off: {off!r} — the off "
                "arm must record a positive rate to price the layer")
    kill = (new.get("control_plane_3proc") or {}).get("kill") or {}
    if kill.get("completed") and ("flight_dumps" in kill
                                  or "flight_merge_ok" in kill):
        if (kill.get("flight_dumps") or 0) < FLIGHT_SURVIVORS:
            problems.append(
                f"FLIGHT-DUMP control_plane_3proc/kill: "
                f"{kill.get('flight_dumps')!r} flight dumps for "
                f"{FLIGHT_SURVIVORS} survivors — every survivor must "
                "leave its black box")
        if not kill.get("flight_merge_ok"):
            problems.append(
                f"FLIGHT-DUMP control_plane_3proc/kill: flight_merge_"
                f"ok={kill.get('flight_merge_ok')!r} — the merge CLI "
                "must reconstruct the failure timeline (exit 0)")
    return problems


SERVE_P99_SLACK = 2.5  # storm on-arm p99 guard vs the off arm. On the
# 2-core CI container both arms' latency TAILS are scheduler noise
# (single reps swing 4x run to run; the PR1 overlap caveat applies),
# and the on arm's readers complete ~5x more requests, so their
# residual wire pulls queue behind genuinely more work — the measured
# honest ratio is ~1.3-2x AT HIGHER THROUGHPUT, a closed-loop
# throughput/latency tradeoff, not a regression. reads/sec and p50
# separate the arms robustly (a local replica hit is ~free), so those
# gate strictly; the p99 guard sits at 2.5x to catch the
# integer-factor failure classes (a sleep/lock on the replica serve
# path, refusal loops re-routing every leg twice) without flaking on
# the tradeoff band.


def serve_tripwires(new: dict) -> list[str]:
    """Absolute (prior-free) gates on the ``pull_storm_3proc`` sweep
    (the serving plane); vacuous when the sweep is absent.

    - SERVE-SLO: the replicas-ON storm arm must hold read rows/sec at
      or above the off arm (10% drift band; strictly above is the
      commit-time acceptance the artifact records) and beat it on
      median pull latency, with p99 inside ``SERVE_P99_SLACK``, and
      must actually have served rows from replicas — a zero replica
      count means the plane silently fell off (env plumbing,
      promotion dead, leases never granted) while the run still
      completes.
    - SERVE-STALE: zero reads older than ``clk − s`` recorded by any
      storm arm (every consumed reply re-checks the admission rule its
      serve claimed; a nonzero counter is a protocol bug, never load).
    - SERVE-SHED: the admission-throttled arm must COMPLETE with the
      shed path exercised (redirects/backpressure > 0) — refusal must
      degrade to explicit retry, never to a timeout poison."""
    grid = new.get("pull_storm_3proc") or {}
    if not grid:
        return []
    problems = []
    off = grid.get("off") or {}
    on = grid.get("on") or {}
    if not on.get("completed") or not off.get("completed"):
        problems.append(
            f"SERVE-SLO pull_storm_3proc: off completed="
            f"{off.get('completed')!r} on completed="
            f"{on.get('completed')!r} — the storm arms must complete")
        return problems
    rep_rows = (on.get("replica_local_rows") or 0) \
        + (on.get("replica_wire_rows") or 0)
    if not rep_rows:
        problems.append(
            "SERVE-SLO pull_storm_3proc/on: 0 replica-served rows — "
            "the serving plane is silently disabled")
    # the commit-time acceptance is reads STRICTLY above (the
    # committed artifact records it); the standing gate tolerates a
    # 10% drift band — the off arm is one hot owner's serve rate and
    # swings run-to-run, and the 'plane silently fell off' mode
    # (on == off exactly) is the replica-rows check's job above. What
    # this trips on is replication actively COSTING read throughput.
    r_off, r_on = off.get("read_rows_per_sec"), \
        on.get("read_rows_per_sec")
    if not (isinstance(r_off, (int, float))
            and isinstance(r_on, (int, float))
            and r_on >= r_off * 0.9):
        problems.append(
            f"SERVE-SLO pull_storm_3proc: on-arm reads {r_on!r} below "
            f"the off arm's {r_off!r} rows/s (beyond the 10% drift "
            "band) — replica fan-out is costing read throughput")
    p50_off, p50_on = off.get("pull_p50_ms"), on.get("pull_p50_ms")
    if isinstance(p50_off, (int, float)) \
            and isinstance(p50_on, (int, float)) and p50_on > p50_off:
        problems.append(
            f"SERVE-SLO pull_storm_3proc: on-arm p50 {p50_on} ms above "
            f"off-arm {p50_off} ms — local replica serving is not "
            "cutting the median read latency")
    p99_off, p99_on = off.get("pull_p99_ms"), on.get("pull_p99_ms")
    if isinstance(p99_off, (int, float)) and p99_off > 0 \
            and isinstance(p99_on, (int, float)) \
            and p99_on > p99_off * SERVE_P99_SLACK:
        problems.append(
            f"SERVE-SLO pull_storm_3proc: on-arm p99 {p99_on} ms "
            f"beyond {SERVE_P99_SLACK}x the off arm's {p99_off} ms — "
            "the serve plane is taxing the read tail")
    for arm in ("on", "shed"):
        a = grid.get(arm) or {}
        if a.get("stale_reads"):
            problems.append(
                f"SERVE-STALE pull_storm_3proc/{arm}: "
                f"{a['stale_reads']} reads staler than the admission "
                "bound — the snapshot stamp protocol is broken")
    shed = grid.get("shed") or {}
    if not shed.get("completed"):
        problems.append(
            f"SERVE-SHED pull_storm_3proc/shed: completed="
            f"{shed.get('completed')!r} — admission throttling must "
            "degrade to explicit refusal, never a timeout poison")
    elif not ((shed.get("shed_redirects") or 0)
              + (shed.get("backpressure") or 0)):
        problems.append(
            "SERVE-SHED pull_storm_3proc/shed: 0 shed/backpressure "
            "events with the bucket throttled — admission control is "
            "silently disabled")
    return problems


def elastic_tripwires(new: dict) -> list[str]:
    """Absolute (prior-free) gates on the ``elastic_membership_3proc``
    sweep (balance/membership.py); vacuous when the sweep is absent.
    All three arms are COMPLETION gates — their rates live under
    gate-invisible keys (``steps_per_sec_elastic``) like every chaos
    arm's, so none enters the run-to-run ±10% comparison.

    - ELASTIC-DEAD: the seeded-SIGKILL arm's survivors must COMPLETE
      with >= 1 range restored from the elastic checkpoint, zero
      unrecovered frames, a finite final loss, and bitwise-agreeing
      finals — a kill that survives without restoring anything means
      the death path silently fell off, and one that restores but
      diverges means the fence/restore protocol is torn.
    - ELASTIC-JOIN: the standby-admission arm must COMPLETE with the
      joiner serving > 0 rows — a join that 'works' while the joiner
      owns nothing is the silently-disabled failure mode of the admit
      plan.
    - The steady (armed-idle) arm must complete cleanly: the plane may
      not tax correctness when nothing joins or leaves."""
    grid = new.get("elastic_membership_3proc") or {}
    if not grid:
        return []
    problems = []
    steady = grid.get("steady") or {}
    if not steady.get("completed"):
        problems.append(
            f"ELASTIC-DEAD elastic_membership_3proc/steady: completed="
            f"{steady.get('completed')!r} — an armed-but-idle fleet "
            "must complete cleanly")
    kill = grid.get("kill") or {}
    if not kill.get("completed"):
        problems.append(
            f"ELASTIC-DEAD elastic_membership_3proc/kill: completed="
            f"{kill.get('completed')!r} — the seeded-SIGKILL arm's "
            "survivors must finish the run (death should degrade to "
            "reduced capacity, not a poisoned job)")
    else:
        if not kill.get("blocks_restored"):
            problems.append(
                "ELASTIC-DEAD elastic_membership_3proc/kill: 0 ranges "
                "restored from the elastic checkpoint — the death "
                "path is silently disabled")
        if kill.get("wire_frames_lost", 0):
            problems.append(
                f"ELASTIC-DEAD elastic_membership_3proc/kill: "
                f"{kill['wire_frames_lost']} unrecovered frames — the "
                "transition is leaking wire loss")
        loss = kill.get("loss_last")
        if not (isinstance(loss, (int, float))
                and loss == loss and abs(loss) != float("inf")):
            problems.append(
                f"ELASTIC-DEAD elastic_membership_3proc/kill: final "
                f"loss {loss!r} is not finite — the restored state is "
                "poisoning training")
        if not kill.get("finals_agree"):
            problems.append(
                "ELASTIC-DEAD elastic_membership_3proc/kill: "
                "survivors' final tables disagree — the restore/fence "
                "protocol is torn")
    join = grid.get("join") or {}
    if not join.get("completed"):
        problems.append(
            f"ELASTIC-JOIN elastic_membership_3proc/join: completed="
            f"{join.get('completed')!r} — the standby-admission arm "
            "must finish with the joiner in the fleet")
    elif not join.get("joiner_serve_rows"):
        problems.append(
            "ELASTIC-JOIN elastic_membership_3proc/join: the joiner "
            "served 0 rows — it was admitted but owns nothing (the "
            "admit plan is silently disabled)")
    return problems


def control_plane_tripwires(new: dict) -> list[str]:
    """Absolute (prior-free) gates on the ``control_plane_3proc``
    sweep (coordinator lease failover + the closed-loop autoscaler —
    balance/control_plane.py, balance/autoscaler.py); vacuous when the
    sweep is absent. Every arm is a COMPLETION gate: rates live under
    the gate-invisible ``steps_per_sec_ctrl`` key (the chaos-arm
    convention), so none enters the run-to-run ±10% comparison.

    - CTRL-FAILOVER: the coordinator-kill arm's survivors must
      COMPLETE the full step count (zero lost steps) with the lease
      advanced EXACTLY once (every survivor at term 1 — zero means
      succession silently fell off, two means it flapped), >= 1 range
      restored from the elastic checkpoint, zero unrecovered frames,
      and bitwise-agreeing finals.
    - CTRL-SCALE: the storm-autoscale arm must COMPLETE with >= 1
      autoscaler admit and >= 1 drain (the closed loop actually
      closed), a recorded positive pre-admit shed rate (the admit
      happened UNDER measured load, not by coincidence), and the
      post-admit rate — the calm-streak mean that triggered the drain
      — at or below it: shed pressure measurably FELL after the admit
      before the loop shrank the fleet, so both actions were signal-
      driven, not timer-driven.
    - The steady (armed-idle) arm must complete with ZERO membership
      changes: a calm fleet may not flap (hysteresis honesty)."""
    grid = new.get("control_plane_3proc") or {}
    if not grid:
        return []
    problems = []
    steady = grid.get("steady") or {}
    if not steady.get("completed"):
        problems.append(
            f"CTRL-FAILOVER control_plane_3proc/steady: completed="
            f"{steady.get('completed')!r} — an armed-but-idle control "
            "plane must complete cleanly")
    elif (steady.get("joins") or steady.get("leaves")
          or steady.get("admits") or steady.get("drains")):
        problems.append(
            f"CTRL-SCALE control_plane_3proc/steady: membership "
            f"changed on a calm run (joins={steady.get('joins')!r} "
            f"leaves={steady.get('leaves')!r} "
            f"admits={steady.get('admits')!r} "
            f"drains={steady.get('drains')!r}) — the autoscaler is "
            "flapping without load")
    kill = grid.get("kill") or {}
    if not kill.get("completed"):
        problems.append(
            f"CTRL-FAILOVER control_plane_3proc/kill: completed="
            f"{kill.get('completed')!r} — the coordinator-kill arm's "
            "survivors must finish under the successor (holder death "
            "should degrade to a lease handover, not a gang restart)")
    else:
        if kill.get("lease_term") != 1 or not kill.get("terms_agree"):
            problems.append(
                f"CTRL-FAILOVER control_plane_3proc/kill: lease_term="
                f"{kill.get('lease_term')!r} terms_agree="
                f"{kill.get('terms_agree')!r} — the successor must be "
                "elected exactly once (0 = succession silently "
                "disabled, > 1 = the lease flapped)")
        if kill.get("clock_min") != kill.get("iters"):
            problems.append(
                f"CTRL-FAILOVER control_plane_3proc/kill: clock_min="
                f"{kill.get('clock_min')!r} of iters="
                f"{kill.get('iters')!r} — steps were lost across the "
                "failover")
        if not kill.get("blocks_restored"):
            problems.append(
                "CTRL-FAILOVER control_plane_3proc/kill: 0 ranges "
                "restored — the successor never issued the old "
                "holder's death plan")
        if kill.get("wire_frames_lost", 0):
            problems.append(
                f"CTRL-FAILOVER control_plane_3proc/kill: "
                f"{kill['wire_frames_lost']} unrecovered frames — the "
                "handover is leaking wire loss")
        if not kill.get("finals_agree"):
            problems.append(
                "CTRL-FAILOVER control_plane_3proc/kill: survivors' "
                "final tables disagree — the restore/fence protocol "
                "is torn across the failover")
    storm = grid.get("storm") or {}
    if not storm.get("completed"):
        problems.append(
            f"CTRL-SCALE control_plane_3proc/storm: completed="
            f"{storm.get('completed')!r} — the storm-autoscale arm "
            "must finish (shed bursts should scale the fleet, not "
            "poison the run)")
    else:
        if not storm.get("admits"):
            problems.append(
                "CTRL-SCALE control_plane_3proc/storm: 0 autoscaler "
                "admits under a shedding storm — the scale-up signal "
                "path is silently disabled")
        if not storm.get("drains"):
            problems.append(
                "CTRL-SCALE control_plane_3proc/storm: 0 autoscaler "
                "drains after the storm ebbed — the scale-down half "
                "of the loop never closed")
        pre = storm.get("shed_rate_pre")
        post = storm.get("shed_rate_post")
        if not (isinstance(pre, (int, float)) and pre > 0):
            problems.append(
                f"CTRL-SCALE control_plane_3proc/storm: shed_rate_pre="
                f"{pre!r} — the admit fired without recorded shed "
                "load (the signal wire is broken)")
        elif not (isinstance(post, (int, float)) and post <= pre):
            problems.append(
                f"CTRL-SCALE control_plane_3proc/storm: post-admit "
                f"shed rate {post!r} did not fall from pre-admit "
                f"{pre!r} — the admitted capacity absorbed nothing "
                "(heat-aware placement silently disabled?)")
    return problems


def partition_tripwires(new: dict) -> list[str]:
    """Absolute (prior-free) gates on the ``partition_3proc`` sweep
    (link-level chaos partitions + quorum fencing + graceful lease
    handover — comm/chaos.py part= entries, balance/control_plane.py,
    balance/membership.py); vacuous when the sweep is absent. Every
    arm is a COMPLETION gate (rates under ``steps_per_sec_ctrl``).

    - PARTITION-FENCE: on the fence/heal arm, the minority-side
      ex-coordinator must end FENCED OUT (convicted alive, exits via
      the fenced_out poison, never a silent zombie) and its stale-term
      plan — journaled behind the cut, recovered post-heal — must be
      DROPPED at >= 1 survivor (``fenced_total`` counts the lease
      ``fenced`` + rebalancer ``stale_plans_fenced`` sums); the lease
      must sit at term 1 exactly (the quorum minted one term, the
      minority minted none).
    - PARTITION-HEAL: the same arm's survivors must complete the full
      step count with ZERO unrecovered frames (the partition's cut
      frames all recovered or fenced — never silently lost; the
      reliable reopen path exists for exactly this) and bitwise-
      agreeing finals; the injector must have provably engaged
      (``part_dropped`` > 0 — a window that never opened gates
      nothing). ``reliable_reopened`` is recorded but NOT gated:
      whether a gap's budget exhausts inside the cut (and so needs
      the reopen) depends on whether any gap opened BEFORE the cut —
      timing the drill cannot pin; the reopen mechanics are pinned by
      the tests/test_partition_plane.py protocol regressions instead.
    - HANDOVER: the holder-self-drain arm must complete with the term
      advanced EXACTLY once (the voluntary transfer — zero means the
      holder never handed over, two means something also died), ZERO
      deaths (nobody was convicted during a graceful drain), the
      leaver exiting rc 0 via the drain path, zero unrecovered
      frames, and bitwise survivor agreement."""
    grid = new.get("partition_3proc") or {}
    if not grid:
        return []
    problems = []
    fence = grid.get("fence_heal") or {}
    if not fence.get("completed"):
        problems.append(
            f"PARTITION-FENCE partition_3proc/fence_heal: completed="
            f"{fence.get('completed')!r} — the asymmetric-partition "
            "arm's survivors must finish under the quorum successor")
    else:
        if not fence.get("ex_coord_fenced_out"):
            problems.append(
                "PARTITION-FENCE partition_3proc/fence_heal: the "
                "minority ex-coordinator did not exit fenced_out — a "
                "convicted-but-alive rank kept running (zombie "
                "writes)")
        if not fence.get("fenced_total"):
            problems.append(
                "PARTITION-FENCE partition_3proc/fence_heal: 0 "
                "stale-term frames fenced at the survivors — the "
                "ex-coordinator's recovered plan was adopted (or "
                "never recovered: both break the drill's claim)")
        if fence.get("lease_term") != 1 or not fence.get("terms_agree"):
            problems.append(
                f"PARTITION-FENCE partition_3proc/fence_heal: "
                f"lease_term={fence.get('lease_term')!r} terms_agree="
                f"{fence.get('terms_agree')!r} — the quorum must mint "
                "exactly one term (the minority island none)")
        if fence.get("clock_min") != fence.get("iters"):
            problems.append(
                f"PARTITION-HEAL partition_3proc/fence_heal: "
                f"clock_min={fence.get('clock_min')!r} of iters="
                f"{fence.get('iters')!r} — survivors lost steps "
                "across the partition")
        if fence.get("wire_frames_lost", 0):
            problems.append(
                f"PARTITION-HEAL partition_3proc/fence_heal: "
                f"{fence['wire_frames_lost']} unrecovered frames — "
                "the heal leaked wire loss (reopen path broken?)")
        if not fence.get("part_dropped"):
            problems.append(
                "PARTITION-HEAL partition_3proc/fence_heal: "
                "part_dropped=0 — the partition injector never "
                "engaged, the arm proved nothing")
        if not fence.get("finals_agree"):
            problems.append(
                "PARTITION-HEAL partition_3proc/fence_heal: "
                "survivors' final tables disagree after the heal")
    ho = grid.get("handover") or {}
    if not ho.get("completed"):
        problems.append(
            f"HANDOVER partition_3proc/handover: completed="
            f"{ho.get('completed')!r} — the holder-self-drain arm "
            "must finish under the successor")
    else:
        if ho.get("lease_term") != 1 or not ho.get("terms_agree"):
            problems.append(
                f"HANDOVER partition_3proc/handover: lease_term="
                f"{ho.get('lease_term')!r} terms_agree="
                f"{ho.get('terms_agree')!r} — a graceful handover "
                "advances the term exactly once")
        if ho.get("deaths", 0):
            problems.append(
                f"HANDOVER partition_3proc/handover: {ho['deaths']} "
                "death verdicts during a graceful drain — the "
                "handover raced the failure detector")
        if ho.get("clock_min") != ho.get("iters"):
            problems.append(
                f"HANDOVER partition_3proc/handover: clock_min="
                f"{ho.get('clock_min')!r} of iters="
                f"{ho.get('iters')!r} — survivors lost steps across "
                "the handover")
        if not ho.get("leaver_drained"):
            problems.append(
                "HANDOVER partition_3proc/handover: the ex-holder "
                "did not exit via the drain path (rc 0 + drained "
                "event) — poisoned instead")
        if ho.get("wire_frames_lost", 0):
            problems.append(
                f"HANDOVER partition_3proc/handover: "
                f"{ho['wire_frames_lost']} unrecovered frames")
        if not ho.get("finals_agree"):
            problems.append(
                "HANDOVER partition_3proc/handover: survivors' final "
                "tables disagree after the handover")
    return problems


def fail_slow_tripwires(new: dict) -> list[str]:
    """Absolute (prior-free) gates on the ``fail_slow_3proc`` sweep
    (fail-slow detection + hedged reads + quorum-fenced demotion —
    obs/slowness.py, serve/hedge.py, the rebalancer's demote pass);
    vacuous when the sweep is absent. Every arm is a COMPLETION gate
    (rates under the gate-invisible ``steps_per_sec_slow``).

    - SLOW-HEDGE: both the unmitigated and hedged arms must complete
      under the injection (a slow-but-alive rank poisons NOTHING —
      that is the pre-mitigation baseline this repo already held);
      the injector must have provably engaged on both
      (``slowed`` > 0); the hedged arm must have actually hedged
      (``hedges_fired`` > 0 — a zero here means the plane silently
      disarmed and any p99 win is a fluke) and its designated
      reader's warmed windowed read p99 must sit STRICTLY below the
      unmitigated arm's.
    - SLOW-DRAIN: the demote arm must complete every step
      (clock_min == iters: demotion loses zero steps) with >= 1
      quorum slow verdict reached, >= 1 hot block migrated OFF the
      sick rank, zero unrecovered frames, bitwise-agreeing finals,
      and the four fail-slow flight events present in the merged
      post-mortem boxes (slow_suspect → slow_verdict → hedge_fired →
      demote — the black box must tell the story with zero
      pre-arming).
    - SLOW-IDLE: the armed-idle lockstep drill (hedge plane on, no
      slow link) must report bitwise-equal finals over > 0 rows —
      arming the mitigation may not perturb one bit of a healthy
      run."""
    grid = new.get("fail_slow_3proc") or {}
    if not grid:
        return []
    problems = []
    unm = grid.get("unmitigated") or {}
    hed = grid.get("hedged") or {}
    if not unm.get("completed"):
        problems.append(
            f"SLOW-HEDGE fail_slow_3proc/unmitigated: completed="
            f"{unm.get('completed')!r} — a slow-but-alive rank must "
            "degrade reads, never poison the run")
    if not hed.get("completed"):
        problems.append(
            f"SLOW-HEDGE fail_slow_3proc/hedged: completed="
            f"{hed.get('completed')!r} — the hedged arm must finish")
    if unm.get("completed") and hed.get("completed"):
        if not unm.get("slowed") or not hed.get("slowed"):
            problems.append(
                f"SLOW-HEDGE fail_slow_3proc: slowed="
                f"{unm.get('slowed')!r}/{hed.get('slowed')!r} — the "
                "slow# injector never engaged, the arms prove nothing")
        if not hed.get("hedges_fired"):
            problems.append(
                "SLOW-HEDGE fail_slow_3proc/hedged: 0 hedges fired — "
                "the hedge plane silently disarmed (any p99 win would "
                "be replicas alone)")
        up99, hp99 = unm.get("reader_p99_ms"), hed.get("reader_p99_ms")
        if not (isinstance(up99, (int, float))
                and isinstance(hp99, (int, float)) and hp99 < up99):
            problems.append(
                f"SLOW-HEDGE fail_slow_3proc: hedged reader p99 "
                f"{hp99!r} ms not strictly below unmitigated "
                f"{up99!r} ms — the read mitigation bought nothing")
    dem = grid.get("demote") or {}
    if not dem.get("completed"):
        problems.append(
            f"SLOW-DRAIN fail_slow_3proc/demote: completed="
            f"{dem.get('completed')!r} — the demote arm must finish "
            "(demotion is a migration, not a failure)")
    else:
        if dem.get("clock_min") != grid.get("iters"):
            problems.append(
                f"SLOW-DRAIN fail_slow_3proc/demote: clock_min="
                f"{dem.get('clock_min')!r} of iters="
                f"{grid.get('iters')!r} — demotion lost steps")
        if not dem.get("slow_verdicts"):
            problems.append(
                "SLOW-DRAIN fail_slow_3proc/demote: 0 quorum slow "
                "verdicts — detection never convicted the seeded sick "
                "rank")
        if not dem.get("sick_blocks_out"):
            problems.append(
                "SLOW-DRAIN fail_slow_3proc/demote: 0 blocks migrated "
                "off the sick rank — the demote pass never moved its "
                "hot blocks")
        if dem.get("wire_frames_lost", 0):
            problems.append(
                f"SLOW-DRAIN fail_slow_3proc/demote: "
                f"{dem['wire_frames_lost']} unrecovered frames")
        if not dem.get("finals_agree"):
            problems.append(
                "SLOW-DRAIN fail_slow_3proc/demote: survivors' final "
                "tables disagree after demotion")
        if not dem.get("flight_events_ok"):
            problems.append(
                f"SLOW-DRAIN fail_slow_3proc/demote: flight boxes "
                f"missing fail-slow events (got "
                f"{dem.get('flight_events')!r}; need slow_suspect, "
                "slow_verdict, hedge_fired, demote) — the post-mortem "
                "cannot tell the story")
    idle = grid.get("idle") or {}
    if not idle.get("equal") or not idle.get("rows_checked"):
        problems.append(
            f"SLOW-IDLE fail_slow_3proc/idle: equal="
            f"{idle.get('equal')!r} rows_checked="
            f"{idle.get('rows_checked')!r}"
            + (f" error={idle.get('error')!r}" if idle.get("error")
               else "")
            + " — armed-idle hedging must be bitwise-equal to off")
    elif idle.get("hedges_fired", 0):
        # bitwise-equal AND hedges fired would mean loopback replicas
        # happened to serve identical rows — equal by luck, not by
        # the min_ms floor keeping the plane idle
        problems.append(
            f"SLOW-IDLE fail_slow_3proc/idle: {idle['hedges_fired']} "
            "hedges fired on a clean wire — armed-IDLE means the "
            "min_ms floor keeps every leg unhedged")
    return problems


def reshard_tripwires(new: dict) -> list[str]:
    """Absolute (prior-free) gates on the ``reshard_3proc`` sweep
    (planned collective redistribution — balance/redistribute.py, the
    trainer's slice rounds, ckpt/elastic's streaming restore); vacuous
    when the sweep is absent.

    - RESHARD-MEM: memory-boundedness must be MEASURED, twice. The
      streaming-restore drill (``mem``): capped read bitwise-equal to
      the uncapped read, measured peak staging within the cap, and the
      legacy whole-member staging provably ABOVE it at the same size.
      The live wire (``drain_planned`` vs ``drain_p2p``): the same
      whole-rank drain must move the same blocks both ways, the
      planned arm's measured per-round peak within the cap, and the
      p2p arm's one-shot staging above it — no cap, no claim.
    - RESHARD-SAFE: every chaos arm completes with zero unrecovered
      frames and bitwise-agreeing survivors. ``kill`` (gainer
      SIGKILLed mid-run, planner + eager rebalancer armed) must
      restore >= 1 block from the elastic checkpoint; ``part`` (the
      sender->gainer link cut across the drain window) must still
      drain the leaver, ship >= 1 slice, and leave the
      ``reshard_round`` evidence in the zero-pre-arming flight
      boxes."""
    grid = new.get("reshard_3proc") or {}
    if not grid:
        return []
    problems = []
    cap = grid.get("cap") or 0
    mem = grid.get("mem") or {}
    if not mem.get("equal"):
        problems.append(
            f"RESHARD-MEM reshard_3proc/mem: equal={mem.get('equal')!r}"
            + (f" error={mem.get('error')!r}" if mem.get("error")
               else "")
            + " — the cap-bounded streaming restore must be bitwise-"
            "equal to the uncapped read")
    else:
        mp, mb = mem.get("peak_planned"), mem.get("peak_p2p")
        mc = mem.get("cap") or 0
        if not (isinstance(mp, int) and 0 < mp <= mc):
            problems.append(
                f"RESHARD-MEM reshard_3proc/mem: measured peak "
                f"{mp!r} B outside (0, cap={mc}] — streaming never "
                "engaged or the cap is a promise, not a measurement")
        if not (isinstance(mb, int) and mb > mc):
            problems.append(
                f"RESHARD-MEM reshard_3proc/mem: legacy whole-member "
                f"peak {mb!r} B not above cap={mc} — the table is too "
                "small for the drill to prove anything")
    pl, pp = grid.get("drain_planned") or {}, grid.get("drain_p2p") or {}
    part = grid.get("part") or {}
    for name, arm in (("drain_planned", pl), ("drain_p2p", pp),
                      ("part", part)):
        if not arm.get("completed"):
            problems.append(
                f"RESHARD-SAFE reshard_3proc/{name}: completed="
                f"{arm.get('completed')!r} — a whole-rank drain is a "
                "migration, not a failure"
                + (f" ({arm.get('error')!r})" if arm.get("error")
                   else ""))
            continue
        if not arm.get("leaver_drained"):
            problems.append(
                f"RESHARD-SAFE reshard_3proc/{name}: the leaver never "
                "reached its drained exit")
        if arm.get("wire_frames_lost", 0):
            problems.append(
                f"RESHARD-SAFE reshard_3proc/{name}: "
                f"{arm['wire_frames_lost']} unrecovered frames")
        if not arm.get("finals_agree"):
            problems.append(
                f"RESHARD-SAFE reshard_3proc/{name}: survivors' final "
                "tables disagree after the drain")
    if pl.get("completed") and pp.get("completed"):
        rsh = pl.get("reshard") or {}
        if not (pl.get("blocks_moved") and pp.get("blocks_moved")):
            problems.append(
                f"RESHARD-MEM reshard_3proc: blocks_moved="
                f"{pl.get('blocks_moved')!r}/{pp.get('blocks_moved')!r}"
                " — the drain arms moved nothing, the staging A/B "
                "proves nothing")
        if not rsh.get("slices") or not rsh.get("rounds"):
            problems.append(
                f"RESHARD-MEM reshard_3proc/drain_planned: rounds="
                f"{rsh.get('rounds')!r} slices={rsh.get('slices')!r} "
                "— the planner never shipped a slice round (armed but "
                "routed p2p?)")
        peak_pl = rsh.get("peak_planned")
        peak_pp = pp.get("peak_p2p")
        if not (isinstance(peak_pl, int) and 0 < peak_pl <= cap):
            problems.append(
                f"RESHARD-MEM reshard_3proc/drain_planned: measured "
                f"peak {peak_pl!r} B outside (0, cap={cap}] — the "
                "per-round staging cap did not hold on the live wire")
        if not (isinstance(peak_pp, int) and peak_pp > cap):
            problems.append(
                f"RESHARD-MEM reshard_3proc/drain_p2p: one-shot "
                f"staging {peak_pp!r} B not above cap={cap} — the "
                "shard is too small for the A/B to prove the cap "
                "matters")
        if pp.get("reshard_absent") is False:
            problems.append(
                "RESHARD-MEM reshard_3proc/drain_p2p: reshard "
                "counters present on the baseline arm — the planner "
                "leaked into the p2p arm, the A/B compares planned "
                "vs planned")
    kill = grid.get("kill") or {}
    if not kill.get("completed"):
        problems.append(
            f"RESHARD-SAFE reshard_3proc/kill: completed="
            f"{kill.get('completed')!r} — survivors of a mid-run "
            "gainer SIGKILL must finish"
            + (f" ({kill.get('error')!r})" if kill.get("error")
               else ""))
    else:
        if not kill.get("blocks_restored"):
            problems.append(
                "RESHARD-SAFE reshard_3proc/kill: 0 blocks restored — "
                "the dead gainer's ranges never came back from the "
                "elastic checkpoint")
        if kill.get("wire_frames_lost", 0):
            problems.append(
                f"RESHARD-SAFE reshard_3proc/kill: "
                f"{kill['wire_frames_lost']} unrecovered frames")
        if not kill.get("finals_agree"):
            problems.append(
                "RESHARD-SAFE reshard_3proc/kill: survivors' final "
                "tables disagree after the kill")
    if part.get("completed"):
        if not (part.get("reshard") or {}).get("slices"):
            problems.append(
                "RESHARD-SAFE reshard_3proc/part: 0 slices shipped — "
                "the cut arm never exercised the planner")
        if not part.get("flight_events_ok"):
            problems.append(
                f"RESHARD-SAFE reshard_3proc/part: flight boxes "
                f"missing reshard_round (got "
                f"{part.get('flight_events')!r}) — the post-mortem "
                "cannot tell the redistribution story")
    return problems


def hier_tripwires(new: dict) -> list[str]:
    """Absolute (prior-free) gates on the ``hier_agg_3proc`` sweep
    (the two-level topology-aware push tree, balance/hier.py);
    vacuous when the sweep is absent.

    - HIER-WIN: both arms (tree vs accounting-only flat, SAME seeded
      workload) must complete with zero unrecovered frames and
      bitwise-agreeing finals; the tree must have provably engaged
      (``agg_frames`` > 0, ``contribs`` > 0, zero fallbacks on the
      clean wire); the flat arm's cross-host leader-leg bytes must be
      >= 1.7x the tree's (``l2_bytes_ratio`` — the whole point: one
      union frame per host per owner instead of per-worker copies);
      the arms' loss trajectories must match (within 5% at the last
      window — aggregation relocates error feedback, it must not
      change what the model learns); and the compression-off bitwise
      drill must report equal finals with the tree provably on
      (``agg_frames`` > 0 in the stamp).
    - HIER-IDLE: the armed-idle drill (``MINIPS_HIER=1``, group=1 —
      no pair in hier mode) must report bitwise-equal finals over
      > 0 rows with ZERO aggregate frames — arming the layer may not
      perturb one bit of a flat-topology run."""
    grid = new.get("hier_agg_3proc") or {}
    if not grid:
        return []
    problems = []
    hier = grid.get("hier") or {}
    flat = grid.get("flat") or {}
    for name, a in (("hier", hier), ("flat", flat)):
        if not a.get("completed"):
            problems.append(
                f"HIER-WIN hier_agg_3proc/{name}: completed="
                f"{a.get('completed')!r} — both arms must finish on "
                "the clean wire")
        else:
            if a.get("wire_frames_lost", 0):
                problems.append(
                    f"HIER-WIN hier_agg_3proc/{name}: "
                    f"{a['wire_frames_lost']} unrecovered frames")
            if not a.get("finals_agree"):
                problems.append(
                    f"HIER-WIN hier_agg_3proc/{name}: final tables "
                    "disagree across ranks")
    if hier.get("completed") and flat.get("completed"):
        if not hier.get("agg_frames") or not hier.get("contribs"):
            problems.append(
                f"HIER-WIN hier_agg_3proc/hier: agg_frames="
                f"{hier.get('agg_frames')!r} contribs="
                f"{hier.get('contribs')!r} — the tree never engaged, "
                "any byte win is mislabeled flat traffic")
        if hier.get("fallbacks", 0):
            problems.append(
                f"HIER-WIN hier_agg_3proc/hier: {hier['fallbacks']} "
                "fallbacks on a clean wire — the leader lane is sick "
                "and the arms are not comparable")
        ratio = grid.get("l2_bytes_ratio")
        if not (isinstance(ratio, (int, float)) and ratio >= 1.7):
            problems.append(
                f"HIER-WIN hier_agg_3proc: l2_bytes_ratio={ratio!r} "
                "< 1.7 — the leader leg is not earning its keep "
                "(flat cross-host bytes / tree cross-host bytes)")
        hl, fl = hier.get("loss_last"), flat.get("loss_last")
        if not (isinstance(hl, (int, float))
                and isinstance(fl, (int, float))
                and abs(hl - fl) <= 0.05 * max(abs(fl), 1e-9)):
            problems.append(
                f"HIER-WIN hier_agg_3proc: loss_last {hl!r} (tree) vs "
                f"{fl!r} (flat) diverge > 5% — aggregated error "
                "feedback changed the trajectory")
    bit = grid.get("bitwise") or {}
    if not bit.get("equal") or not bit.get("rows_checked"):
        problems.append(
            f"HIER-WIN hier_agg_3proc/bitwise: equal="
            f"{bit.get('equal')!r} rows_checked="
            f"{bit.get('rows_checked')!r}"
            + (f" error={bit.get('error')!r}" if bit.get("error")
               else "")
            + " — the compression-off tree must be bitwise-equal to "
            "the flat wire")
    elif not bit.get("agg_frames"):
        problems.append(
            "HIER-WIN hier_agg_3proc/bitwise: 0 aggregate frames in "
            "the drill stamp — equal because the tree silently "
            "disarmed, not because aggregation is exact")
    idle = grid.get("idle") or {}
    if not idle.get("equal") or not idle.get("rows_checked"):
        problems.append(
            f"HIER-IDLE hier_agg_3proc/idle: equal="
            f"{idle.get('equal')!r} rows_checked="
            f"{idle.get('rows_checked')!r}"
            + (f" error={idle.get('error')!r}" if idle.get("error")
               else "")
            + " — armed-idle (group=1) must be bitwise-equal to off")
    elif idle.get("agg_frames", 0):
        problems.append(
            f"HIER-IDLE hier_agg_3proc/idle: {idle['agg_frames']} "
            "aggregate frames fired under group=1 — armed-IDLE means "
            "no pair is ever in hier mode")
    return problems


def hybrid_tripwires(new: dict) -> list[str]:
    """Absolute (prior-free) gates on the ``hybrid_agg_3proc`` sweep
    (the hybrid data plane: the PR16 tree with the leader's reduce
    moved onto the in-host device mesh, ``MINIPS_HIER agg=mesh``);
    vacuous when the sweep is absent.

    - HYBRID-WIN: both arms (host-agg tree vs mesh-agg hybrid, SAME
      seeded zipf workload, alternating rep medians) must complete
      with zero unrecovered frames; the hybrid arm must have reduced
      on a REAL mesh (``backend_mesh`` = 1, ``mesh_reduces`` > 0,
      zero ``mesh_agg_fallbacks``/``domain_demotions`` on the clean
      wire); its rows/sec/proc must be STRICTLY above the tree's; its
      cross-host leader-leg bytes must be no worse than the tree's
      within 10% (the flush protocol is identical — the tolerance
      absorbs SSP flush-boundary jitter moving dedup opportunities
      between flushes, nothing else; a re-laned wire shows up as 2x);
      and the example-app loss trajectories must match within 5% (the
      speed must not come from different math).
    - HYBRID-IDLE: the armed-idle drill (group=1,agg=mesh) must be
      bitwise-equal to off with ZERO mesh reduces, and the one-device
      DEGENERATE drill bitwise-equal too with the mesh lane provably
      on (``mesh_reduces`` > 0, zero fallbacks) — the degenerate tier
      is THE shared f64 dedup kernel, so off == host == 1-dev mesh."""
    grid = new.get("hybrid_agg_3proc") or {}
    if not grid:
        return []
    problems = []
    tree = grid.get("tree") or {}
    hyb = grid.get("hybrid") or {}
    for name, a in (("tree", tree), ("hybrid", hyb)):
        if not a.get("completed"):
            problems.append(
                f"HYBRID-WIN hybrid_agg_3proc/{name}: completed="
                f"{a.get('completed')!r} — both arms must finish on "
                "the clean wire")
        elif a.get("wire_frames_lost", 0):
            problems.append(
                f"HYBRID-WIN hybrid_agg_3proc/{name}: "
                f"{a['wire_frames_lost']} unrecovered frames")
    if tree.get("completed") and hyb.get("completed"):
        if not hyb.get("backend_mesh") or not hyb.get("mesh_reduces"):
            problems.append(
                f"HYBRID-WIN hybrid_agg_3proc/hybrid: backend_mesh="
                f"{hyb.get('backend_mesh')!r} mesh_reduces="
                f"{hyb.get('mesh_reduces')!r} — the mesh backend "
                "never engaged; the arm is mislabeled host-agg")
        if hyb.get("mesh_agg_fallbacks", 0) \
                or hyb.get("domain_demotions", 0):
            problems.append(
                f"HYBRID-WIN hybrid_agg_3proc/hybrid: "
                f"mesh_agg_fallbacks={hyb.get('mesh_agg_fallbacks')!r} "
                f"domain_demotions={hyb.get('domain_demotions')!r} on "
                "a clean wire — the mesh lane is sick and the arms "
                "are not comparable")
        if tree.get("mesh_reduces", 0):
            problems.append(
                f"HYBRID-WIN hybrid_agg_3proc/tree: "
                f"{tree['mesh_reduces']} mesh reduces in the HOST-agg "
                "arm — the baseline silently ran the hybrid backend")
        tr, hr = (tree.get("rows_per_sec_per_process"),
                  hyb.get("rows_per_sec_per_process"))
        if not (isinstance(tr, (int, float))
                and isinstance(hr, (int, float)) and hr > tr):
            problems.append(
                f"HYBRID-WIN hybrid_agg_3proc: hybrid {hr!r} "
                f"rows/s/proc is not strictly above the host-agg "
                f"tree's {tr!r} — the device reduce is not beating "
                "the host f64 kernel on the seeded point")
        tb, hb = tree.get("l2_tx_bytes"), hyb.get("l2_tx_bytes")
        if not (isinstance(tb, (int, float))
                and isinstance(hb, (int, float)) and tb > 0
                and hb <= 1.10 * tb):
            problems.append(
                f"HYBRID-WIN hybrid_agg_3proc: hybrid cross-host "
                f"bytes {hb!r} exceed the tree's {tb!r} by > 10% — "
                "the reduce backend must not touch the wire (the "
                "tolerance absorbs SSP flush-boundary jitter only)")
    lt, lh = grid.get("loss_tree") or {}, grid.get("loss_hybrid") or {}
    if not lt.get("completed") or not lh.get("completed") \
            or not lt.get("finals_agree") or not lh.get("finals_agree"):
        problems.append(
            f"HYBRID-WIN hybrid_agg_3proc/loss: completed="
            f"({lt.get('completed')!r}, {lh.get('completed')!r}) "
            f"finals_agree=({lt.get('finals_agree')!r}, "
            f"{lh.get('finals_agree')!r}) — the trajectory leg must "
            "finish with rank-agreeing finals in both arms")
    else:
        tl, hl = lt.get("loss_last"), lh.get("loss_last")
        if not (isinstance(tl, (int, float))
                and isinstance(hl, (int, float))
                and abs(hl - tl) <= 0.05 * max(abs(tl), 1e-9)):
            problems.append(
                f"HYBRID-WIN hybrid_agg_3proc: loss_last {hl!r} "
                f"(hybrid) vs {tl!r} (tree) diverge > 5% — the mesh "
                "reduce changed what the model learns")
        if not lh.get("mesh_reduces"):
            problems.append(
                "HYBRID-WIN hybrid_agg_3proc/loss_hybrid: 0 mesh "
                "reduces — the trajectory leg never exercised the "
                "backend it certifies")
    idle = grid.get("idle") or {}
    if not idle.get("equal") or not idle.get("rows_checked"):
        problems.append(
            f"HYBRID-IDLE hybrid_agg_3proc/idle: equal="
            f"{idle.get('equal')!r} rows_checked="
            f"{idle.get('rows_checked')!r}"
            + (f" error={idle.get('error')!r}" if idle.get("error")
               else "")
            + " — armed-idle (group=1,agg=mesh) must be bitwise-equal "
            "to off")
    elif idle.get("mesh_reduces", 0) or idle.get("agg_frames", 0):
        problems.append(
            f"HYBRID-IDLE hybrid_agg_3proc/idle: mesh_reduces="
            f"{idle.get('mesh_reduces')!r} agg_frames="
            f"{idle.get('agg_frames')!r} fired under group=1 — "
            "armed-IDLE means no flush ever runs")
    deg = grid.get("degenerate") or {}
    if not deg.get("equal") or not deg.get("rows_checked"):
        problems.append(
            f"HYBRID-IDLE hybrid_agg_3proc/degenerate: equal="
            f"{deg.get('equal')!r} rows_checked="
            f"{deg.get('rows_checked')!r}"
            + (f" error={deg.get('error')!r}" if deg.get("error")
               else "")
            + " — the one-device mesh must be bitwise-equal to the "
            "host path (THE shared dedup kernel, deposit order "
            "preserved)")
    elif not deg.get("mesh_reduces") or deg.get("mesh_agg_fallbacks",
                                               0):
        problems.append(
            f"HYBRID-IDLE hybrid_agg_3proc/degenerate: mesh_reduces="
            f"{deg.get('mesh_reduces')!r} mesh_agg_fallbacks="
            f"{deg.get('mesh_agg_fallbacks')!r} — equal because the "
            "mesh lane silently disarmed (or fell back), not because "
            "the degenerate tier is exact")
    return problems


def tenant_tripwires(new: dict) -> list[str]:
    """Absolute (prior-free) gates on the ``multi_tenant_3proc`` sweep
    (multi-tenant tables — tenant/registry.py + the per-tenant serve/
    balance splits); vacuous when the sweep is absent.

    - TENANT-ISO: the solo / isolated / shared arms must all complete
      with zero stale reads, zero unrecovered frames, and zero config
      drops; the isolated arm's training-tenant throughput must hold
      within 10% of its solo arm (the SLO bound tenancy promises)
      with the storming tenant provably shedding into its OWN budget
      (inf denied > 0) and the protected tenant's attributed deny
      counters at ZERO; and the shared-bucket contrast arm must show
      the coupling per-tenant buckets remove (trn denied > 0 under
      ``shared=1``) — without it, an "isolation win" proves nothing.
    - TENANT-IDLE: the bare-default-tenant lockstep drill must report
      bitwise-equal finals over > 0 rows with the stamp provably
      engaged (tenant ids [1, 1]) and zero attributed counters —
      arming tenancy may not perturb one bit of a single-tenant run."""
    grid = new.get("multi_tenant_3proc") or {}
    if not grid:
        return []
    problems = []
    arms = {a: grid.get(a) or {} for a in ("solo", "isolated",
                                           "shared")}
    for name, arm in arms.items():
        if not arm.get("completed"):
            problems.append(
                f"TENANT-ISO multi_tenant_3proc/{name}: completed="
                f"{arm.get('completed')!r} — every arm must finish "
                "(tenancy is bookkeeping, never a failure mode)"
                + (f" error={arm.get('error')!r}"
                   if arm.get("error") else ""))
            continue
        if arm.get("stale_reads", 0):
            problems.append(
                f"TENANT-ISO multi_tenant_3proc/{name}: "
                f"{arm['stale_reads']} stale reads — a tenant's own "
                "s bound was violated")
        if arm.get("wire_frames_lost", 0) or arm.get(
                "frames_dropped", 0):
            problems.append(
                f"TENANT-ISO multi_tenant_3proc/{name}: "
                f"wire_frames_lost={arm.get('wire_frames_lost')!r} "
                f"frames_dropped={arm.get('frames_dropped')!r} — "
                "tenancy must not lose or drop one frame")
    solo, iso, sh = arms["solo"], arms["isolated"], arms["shared"]
    if solo.get("completed") and iso.get("completed"):
        s_rate, i_rate = (solo.get("trn_rows_per_sec"),
                          iso.get("trn_rows_per_sec"))
        if not (isinstance(s_rate, (int, float)) and s_rate > 0
                and isinstance(i_rate, (int, float))
                and i_rate >= 0.9 * s_rate):
            problems.append(
                f"TENANT-ISO multi_tenant_3proc: isolated trn rate "
                f"{i_rate!r} below 90% of solo {s_rate!r} — the "
                "noisy neighbor broke the training tenant's SLO")
        if not iso.get("inf_denied"):
            problems.append(
                "TENANT-ISO multi_tenant_3proc/isolated: storm "
                "tenant never denied (inf_denied=0) — the admission "
                "split silently disarmed, the 'isolation' is vacuous")
        if iso.get("trn_denied", 0):
            problems.append(
                f"TENANT-ISO multi_tenant_3proc/isolated: "
                f"trn_denied={iso['trn_denied']} — the protected "
                "tenant was charged for the storm (shed/throttle "
                "must land on the tenant that caused them)")
    if sh.get("completed"):
        if not sh.get("shared"):
            problems.append(
                "TENANT-ISO multi_tenant_3proc/shared: shared=0 — "
                "the contrast arm never armed the fleet bucket")
        if not sh.get("trn_denied"):
            problems.append(
                "TENANT-ISO multi_tenant_3proc/shared: trn_denied=0 "
                "under shared=1 — the coupling the per-tenant split "
                "removes never engaged, the contrast proves nothing")
    idle = grid.get("idle") or {}
    if not idle.get("equal") or not idle.get("rows_checked"):
        problems.append(
            f"TENANT-IDLE multi_tenant_3proc/idle: equal="
            f"{idle.get('equal')!r} rows_checked="
            f"{idle.get('rows_checked')!r}"
            + (f" error={idle.get('error')!r}" if idle.get("error")
               else "")
            + " — the bare default tenant must be bitwise-equal "
            "to tenancy-off")
    else:
        if idle.get("tenant_tids") != [1, 1]:
            problems.append(
                f"TENANT-IDLE multi_tenant_3proc/idle: tenant_tids="
                f"{idle.get('tenant_tids')!r} — equal because the "
                "stamp never engaged, not because armed-idle is free")
        if idle.get("tenant_counters", 0):
            problems.append(
                f"TENANT-IDLE multi_tenant_3proc/idle: "
                f"{idle['tenant_counters']} tenant counters bumped "
                "on an idle run — armed-IDLE means zero attributed "
                "denials")
    return problems


def traffic_tripwires(new: dict) -> list[str]:
    """Absolute (prior-free) gates on the ``million_user_3proc`` sweep
    (the open-loop traffic driver + freshness/SLO observability —
    apps/traffic_driver.py, obs/freshness.py, obs/slo.py); vacuous
    when the sweep is absent.

    - TRAFFIC-FRESH: the base and flash-crowd arms must complete with
      zero request errors, zero stale reads, and zero lost/dropped
      frames (the crowd degrades to LATENCY, never to staleness or
      poison), and both must put ``unissued`` ON THE RECORD —
      arrivals the run ended before issuing are coordinated omission
      unless counted. The BASE arm must issue its whole schedule up
      to a stop-boundary sliver (each dispatcher abandons at most the
      one arrival it had claimed when the run's deadline stopped the
      driver, so the allowance is the summed dispatcher count plus 1%
      of the schedule — more means the base rate was NOT sustainable
      and every latency claim downstream rode an unintended
      overload); the
      CROWD arm may legitimately end with backlog (bounded ``conc``
      cannot drain an 8x burst before the run ends) but its
      scheduled-arrival p99 must sit STRICTLY above bare service p99
      — the queueing delay a closed-loop driver would omit is the
      whole point of the open-loop measurement. Freshness lag samples
      must flow (> 0, with a sane p99 — minutes would mean the stamp
      plumbing broke) and the crowd arm's burning tenant must show
      its promotion budget flexed ABOVE the configured replica count
      (max_budget > configured — "replica budgets ride demand", the
      autoscaler/plane half of ROADMAP item 4).
    - TRAFFIC-SHED: the overload arm's sheds must land in the
      storming tenant's OWN attributed counters (inf denied > 0, trn
      denied = 0) and the burn edge must leave an ``slo_burn``
      flight-recorder box naming that tenant (zero pre-arming: the
      violation IS the post-mortem).
    - TRAFFIC-IDLE: the rate=0 armed driver must be bitwise-equal to
      traffic-off over > 0 rows with ZERO requests scheduled or
      issued — arming the layer may not perturb one bit or one read."""
    grid = new.get("million_user_3proc") or {}
    if not grid:
        return []
    problems = []
    arms = {a: grid.get(a) or {} for a in ("open_loop_base",
                                           "flash_crowd",
                                           "overload_shed")}
    for name, arm in arms.items():
        if not arm.get("completed"):
            problems.append(
                f"TRAFFIC-FRESH million_user_3proc/{name}: completed="
                f"{arm.get('completed')!r} — every arm must finish "
                "(offered load is bounded, overload is shed not fatal)"
                + (f" error={arm.get('error')!r}"
                   if arm.get("error") else ""))
            continue
        if arm.get("stale_reads", 0):
            problems.append(
                f"TRAFFIC-FRESH million_user_3proc/{name}: "
                f"{arm['stale_reads']} stale reads — the crowd must "
                "degrade to latency, never to staleness")
        if arm.get("wire_frames_lost", 0) or arm.get(
                "frames_dropped", 0):
            problems.append(
                f"TRAFFIC-FRESH million_user_3proc/{name}: "
                f"wire_frames_lost={arm.get('wire_frames_lost')!r} "
                f"frames_dropped={arm.get('frames_dropped')!r} — "
                "serving load must not poison the training plane")
    # the latency-not-loss leg: base + crowd issue their WHOLE
    # schedule with zero request errors and live freshness samples
    for name in ("open_loop_base", "flash_crowd"):
        arm = arms[name]
        if not arm.get("completed"):
            continue
        if not arm.get("scheduled"):
            problems.append(
                f"TRAFFIC-FRESH million_user_3proc/{name}: "
                "scheduled=0 — the driver never armed, the arm "
                "proves nothing")
        if "unissued" not in arm:
            problems.append(
                f"TRAFFIC-FRESH million_user_3proc/{name}: unissued "
                "not recorded — arrivals the run ended before "
                "issuing are silent coordinated omission unless "
                "they are counted on the record")
        if arm.get("errors", 0):
            problems.append(
                f"TRAFFIC-FRESH million_user_3proc/{name}: "
                f"errors={arm.get('errors')!r} — issued requests "
                "must succeed (latency absorbs the crowd, not "
                "failed requests)")
        if not arm.get("freshness_samples"):
            problems.append(
                f"TRAFFIC-FRESH million_user_3proc/{name}: "
                "freshness_samples=0 — push-visible-at-replica lag "
                "never measured (stamp plumbing or replication broke)")
        elif not (isinstance(arm.get("freshness_p99_ms"),
                             (int, float))
                  and 0 < arm["freshness_p99_ms"] < 60_000):
            problems.append(
                f"TRAFFIC-FRESH million_user_3proc/{name}: "
                f"freshness_p99_ms={arm.get('freshness_p99_ms')!r} — "
                "visibility lag must be live and under a minute "
                "(refresh-interval-scale, not backlog-scale)")
    base = arms["open_loop_base"]
    if base.get("completed"):
        sliver = (base.get("conc", 0)
                  + max(1, base.get("scheduled", 0) // 100))
        if base.get("unissued", 0) > sliver:
            problems.append(
                f"TRAFFIC-FRESH million_user_3proc/open_loop_base: "
                f"unissued={base['unissued']!r} > stop-boundary "
                f"allowance {sliver} — the base rate must be "
                "sustainable: open-loop arrivals must ALL issue, or "
                "every latency claim downstream rode an unintended "
                "overload")
    crowd = arms["flash_crowd"]
    if crowd.get("completed"):
        sp = crowd.get("sched_p99_ms")
        vp = crowd.get("svc_p99_ms")
        if not (isinstance(sp, (int, float))
                and isinstance(vp, (int, float)) and sp > vp):
            problems.append(
                f"TRAFFIC-FRESH million_user_3proc/flash_crowd: "
                f"sched_p99_ms={sp!r} svc_p99_ms={vp!r} — the "
                "crowd's backlog must show up as queueing delay in "
                "the scheduled-arrival tail; matching tails mean "
                "the crowd never outran the fleet and the open-loop "
                "measurement proved nothing")
        if not (isinstance(crowd.get("inf_max_budget"), int)
                and crowd["inf_max_budget"] > 1):
            problems.append(
                f"TRAFFIC-FRESH million_user_3proc/flash_crowd: "
                f"inf_max_budget={crowd.get('inf_max_budget')!r} "
                "never exceeded the configured 1 replica — the SLO "
                "burn must provably flex the promotion budget")
        if not crowd.get("slo_burns"):
            problems.append(
                "TRAFFIC-FRESH million_user_3proc/flash_crowd: "
                "slo_burns=0 — the crowd never tripped the burn "
                "accounting, the budget-flex 'proof' is vacuous")
    over = arms["overload_shed"]
    if over.get("completed"):
        if not over.get("inf_denied"):
            problems.append(
                "TRAFFIC-SHED million_user_3proc/overload_shed: "
                "inf_denied=0 — overload never shed into the "
                "storming tenant's budget (admission disarmed)")
        if over.get("trn_denied", 0):
            problems.append(
                f"TRAFFIC-SHED million_user_3proc/overload_shed: "
                f"trn_denied={over['trn_denied']} — the training "
                "tenant was charged for serving overload")
        if not over.get("flight_slo_burns"):
            problems.append(
                "TRAFFIC-SHED million_user_3proc/overload_shed: no "
                "slo_burn flight events — the burn edge left no "
                "post-mortem box (checkpoint plumbing broke)")
        elif "inf" not in (over.get("flight_burn_tenants") or []):
            problems.append(
                f"TRAFFIC-SHED million_user_3proc/overload_shed: "
                f"flight_burn_tenants="
                f"{over.get('flight_burn_tenants')!r} — the burn "
                "box does not name the burning tenant")
    idle = grid.get("idle") or {}
    if not idle.get("equal") or not idle.get("rows_checked"):
        problems.append(
            f"TRAFFIC-IDLE million_user_3proc/idle: equal="
            f"{idle.get('equal')!r} rows_checked="
            f"{idle.get('rows_checked')!r}"
            + (f" error={idle.get('error')!r}" if idle.get("error")
               else "")
            + " — a rate=0 armed driver must be bitwise-equal to off")
    elif idle.get("traffic_requests", 1) or idle.get(
            "traffic_scheduled", 1):
        problems.append(
            f"TRAFFIC-IDLE million_user_3proc/idle: "
            f"traffic_requests={idle.get('traffic_requests')!r} "
            f"traffic_scheduled={idle.get('traffic_scheduled')!r} — "
            "armed-IDLE means an empty schedule and zero issues")
    return problems


def mesh_tripwires(new: dict) -> list[str]:
    """Absolute (prior-free) gates on the ``mesh_plane_fused`` sweep
    (the in-mesh collective data plane, train/mesh_plane.py); vacuous
    when the sweep is absent (other benches).

    - MESH-WIN: the mesh arm must COMPLETE and beat the host-wire arm's
      rows/sec/rank STRICTLY (alternating medians) on the fused dense
      point — a mesh plane at or below the socket wire means the
      collective path silently degraded to host round-trips. The blk8
      quantized arm must complete too (its rate is recorded, not
      ordered: quantize/dequantize costs compute on CPU; the byte win
      converts on a real interconnect).
    - MESH-BITWISE: the BSP zmq-vs-mesh lockstep drill must have run
      (> 0 rows checked) and reported bitwise-EQUAL finals — the
      consistency contract must survive the transport swap, bit for
      bit, or the plane is not a data plane but a different trainer."""
    grid = new.get("mesh_plane_fused") or {}
    if not grid:
        return []
    problems = []
    wire = (grid.get("wire") or {}).get(METRIC)
    mesh_arm = grid.get("mesh") or {}
    mesh = mesh_arm.get(METRIC)
    if not mesh_arm.get("completed") or \
            not (isinstance(mesh, (int, float))
                 and isinstance(wire, (int, float)) and mesh > wire):
        problems.append(
            f"MESH-WIN mesh_plane_fused: mesh arm {mesh!r} rows/s/rank "
            f"is not strictly above the host-wire arm's {wire!r} "
            f"(completed={mesh_arm.get('completed')!r}) — the "
            "collective data plane is not beating the socket wire on "
            "the fused point")
    blk = grid.get("mesh_blk8") or {}
    if not blk.get("completed"):
        problems.append(
            f"MESH-WIN mesh_plane_fused/mesh_blk8: completed="
            f"{blk.get('completed')!r} — the quantized collective tier "
            "must complete")
    bit = grid.get("bitwise") or {}
    if not bit.get("equal") or not bit.get("rows_checked"):
        problems.append(
            f"MESH-BITWISE mesh_plane_fused/bitwise: equal="
            f"{bit.get('equal')!r} rows_checked="
            f"{bit.get('rows_checked')!r}"
            + (f" error={bit.get('error')!r}" if bit.get("error")
               else "")
            + " — BSP on the mesh plane must be bitwise-equal to the "
            "zmq wire path under the lockstep drill")
    # MESH-SPARSE (this PR): the deposit-buffer A/B at the embedding
    # shape — the COO/segment-sum staging must cut PEAK host deposit
    # bytes >= 4x vs the dense pre-stacked buffers (it scales with
    # touched rows, the dense one with the table) at throughput no
    # worse than 10% below dense (same collective; only the staging
    # layout changes), with the sparse waves provably the ones that
    # ran. Vacuous when the sub-grid is absent (older artifacts).
    sd = grid.get("sparse_deposit")
    if sd is not None:
        dn, sp = sd.get("dense") or {}, sd.get("sparse") or {}
        if not dn.get("completed") or not sp.get("completed"):
            problems.append(
                f"MESH-SPARSE mesh_plane_fused/sparse_deposit: "
                f"completed=({dn.get('completed')!r}, "
                f"{sp.get('completed')!r}) — both deposit arms must "
                "finish")
        else:
            ratio = sd.get("peak_bytes_ratio")
            if not (isinstance(ratio, (int, float)) and ratio >= 4.0):
                problems.append(
                    f"MESH-SPARSE mesh_plane_fused/sparse_deposit: "
                    f"peak_bytes_ratio={ratio!r} < 4.0 — the COO "
                    "staging is not earning its keep at the "
                    "embedding shape (dense peak / sparse peak)")
            rr = sd.get("rows_ratio")
            if not (isinstance(rr, (int, float)) and rr >= 0.90):
                problems.append(
                    f"MESH-SPARSE mesh_plane_fused/sparse_deposit: "
                    f"rows_ratio={rr!r} < 0.90 — the per-wave gather "
                    "is eating more than the staging win is worth")
            if not sp.get("sparse_waves"):
                problems.append(
                    "MESH-SPARSE mesh_plane_fused/sparse_deposit: 0 "
                    "sparse waves in the sparse arm — the peak-byte "
                    "win is mislabeled dense staging")
            if dn.get("sparse_waves", 0):
                problems.append(
                    f"MESH-SPARSE mesh_plane_fused/sparse_deposit: "
                    f"{dn['sparse_waves']} sparse waves in the DENSE "
                    "arm — the baseline silently ran the sparse path")
    return problems


def shape_mismatch(prior: dict, new: dict) -> list[str]:
    """Refuse cross-SHAPE comparisons (satellite): ``device_shape``
    stamps the backend:device-count the mesh arms measured under —
    collective cost scales with the ring, so a mesh point at 8 devices
    is incomparable to one at 3 exactly the way cross-backend rates
    are. Same conventions as :func:`backend_mismatch`: ``unknown`` (the
    probe-failure / mesh-arm-failed sentinel) and a missing stamp warn
    and compare (we cannot refuse what was never recorded)."""
    ps, ns = prior.get("device_shape"), new.get("device_shape")
    if ps == "unknown":
        ps = None
    if ns == "unknown":
        ns = None
    if ps is None or ns is None:
        if ps != ns or (prior.get("device_shape")
                        != new.get("device_shape")):
            print("bench-regression: WARNING — artifact missing a "
                  "usable device_shape stamp (prior="
                  f"{prior.get('device_shape')!r}, new="
                  f"{new.get('device_shape')!r}); cross-shape drift "
                  "undetectable for this pair")
        return []
    if ps != ns:
        return [f"SHAPE-MISMATCH: prior artifact measured at "
                f"{ps!r}, new at {ns!r} — collective rates across "
                "device shapes are incomparable; re-base the artifact "
                "at the new shape instead of comparing"]
    return []


def backend_mismatch(prior: dict, new: dict) -> list[str]:
    """Refuse to compare artifacts measured on different JAX backends
    (satellite): a record taken on the CPU is silently incomparable
    to one taken on a TPU — absolute rates across backends differ by
    integer factors, so every REGRESSED/MISSING verdict would be
    noise. An artifact predating
    the stamp compares with a warning (we cannot refuse what was never
    recorded); re-basing on the new backend is the fix, as with any
    host change."""
    pb, nb = prior.get("jax_backend"), new.get("jax_backend")
    # "unknown" is the probe-failure sentinel bench_sharded_ps stamps
    # when the resolver subprocess dies — a stamp that carries no
    # information, treated exactly like a missing one (warn, compare):
    # a transient probe timeout must not hard-fail the gate
    if pb == "unknown":
        pb = None
    if nb == "unknown":
        nb = None
    if pb is None or nb is None:
        if pb != nb or (prior.get("jax_backend")
                        != new.get("jax_backend")):
            print("bench-regression: WARNING — artifact missing a "
                  "usable jax_backend stamp (prior="
                  f"{prior.get('jax_backend')!r}, new="
                  f"{new.get('jax_backend')!r}); cross-backend drift "
                  "undetectable for this pair")
        return []
    if pb != nb:
        return [f"BACKEND-MISMATCH: prior artifact measured on "
                f"{pb!r}, new on {nb!r} — absolute rates across "
                "backends are incomparable; re-base the artifact on "
                "the new backend instead of comparing"]
    return []


def compare(prior: dict, new: dict, tolerance: float) -> list[str]:
    """Regression report lines; empty means the gate passes."""
    p, n = throughput_points(prior), throughput_points(new)
    problems = []
    for path in sorted(p):
        if path not in n:
            problems.append(f"MISSING  {path}: sweep point dropped "
                            f"(prior {p[path]:.1f} rows/s/proc)")
            continue
        if p[path] <= 0:
            continue  # a zero/failed prior point can't define a floor
        ratio = n[path] / p[path]
        if ratio < 1.0 - tolerance:
            problems.append(
                f"REGRESSED {path}: {p[path]:.1f} -> {n[path]:.1f} "
                f"rows/s/proc ({(1.0 - ratio) * 100.0:.1f}% drop, "
                f"tolerance {tolerance * 100.0:.0f}%)")
    return problems


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("prior", nargs="?", help="prior artifact path")
    ap.add_argument("new", nargs="?", default="BENCH_SHARDED_PS.json",
                    help="new artifact path (default: working tree)")
    ap.add_argument("--against-git", action="store_true",
                    help="prior = git show HEAD:BENCH_SHARDED_PS.json")
    ap.add_argument("--tolerance", type=float, default=0.10,
                    help="max allowed fractional drop (default 0.10)")
    args = ap.parse_args(argv)

    if args.against_git:
        new_path = args.prior or args.new  # lone positional = NEW file
        shown = subprocess.run(
            ["git", "show", "HEAD:BENCH_SHARDED_PS.json"],
            capture_output=True, text=True)
        if shown.returncode != 0:
            print("bench-regression: no committed artifact to compare "
                  "against (first run?) — gate passes vacuously")
            return 0
        prior = json.loads(shown.stdout)
    else:
        if not args.prior:
            ap.error("need PRIOR artifact path (or --against-git)")
        new_path = args.new
        with open(args.prior) as f:
            prior = json.load(f)
    with open(new_path) as f:
        new = json.load(f)

    mismatch = backend_mismatch(prior, new) + shape_mismatch(prior, new)
    if mismatch:
        # cross-backend/shape: run-to-run comparison is refused outright
        # (the absolute tripwires would be as meaningless as the ratios)
        print("\n".join(mismatch), file=sys.stderr)
        return 1
    problems = (compare(prior, new, args.tolerance)
                + cache_tripwires(new) + chaos_tripwires(new)
                + transport_tripwires(new)
                + wire_compression_tripwires(new)
                + rebalance_tripwires(new) + trace_tripwires(new)
                + obs_tripwires(new)
                + serve_tripwires(new) + elastic_tripwires(new)
                + control_plane_tripwires(new)
                + partition_tripwires(new) + fail_slow_tripwires(new)
                + reshard_tripwires(new)
                + hier_tripwires(new) + hybrid_tripwires(new)
                + tenant_tripwires(new)
                + traffic_tripwires(new)
                + mesh_tripwires(new))
    pts = throughput_points(new)
    print(f"bench-regression: {len(pts)} throughput points checked "
          f"against {len(throughput_points(prior))} prior")
    for path in sorted(pts):
        print(f"  {path}: {pts[path]:.1f} rows/s/proc")
    if problems:
        print("\n".join(problems), file=sys.stderr)
        return 1
    print("bench-regression: PASS")
    return 0


if __name__ == "__main__":
    sys.exit(main())
