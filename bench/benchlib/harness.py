"""One run of one cell: set-up, the measured window, the output check,
the result line.

One process holds the cell's chips. Set-up is everything from process
start to the first timed step: interpreter and imports, backend, tables
and weights made on the device from the seed, the pool of batches, the
cell's first three steps (whose readings the output check compares after
the window) and a fixed number of warm-up steps, closed by one
``block_until_ready``. The window then drives that same object.
"""

from __future__ import annotations

import contextlib
import json
import math
import os
import shutil
import sys
import time

from benchlib import check, costs, peaks, spec, trace as tracelib

NO_CHIP_RC = 3
TRACE_DIR = os.path.join(spec.BENCH_DIR, ".trace")


def process_start_time() -> float:
    """Wall-clock time this process was created, from /proc, so that the
    interpreter's own start-up counts as set-up."""
    try:
        with open("/proc/self/stat") as f:
            ticks = float(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        age = uptime - ticks / os.sysconf("SC_CLK_TCK")
        if 0 <= age < 3600:
            return time.time() - age
    except (OSError, ValueError, IndexError):
        pass
    return time.time()


class Phases:
    """Seconds of each set-up phase, in order; printed on standard error."""

    def __init__(self, t_start: float):
        self.t_start = t_start
        self.rows = [("interpreter", time.time() - t_start)]

    @contextlib.contextmanager
    def __call__(self, name: str):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.rows.append((name, time.perf_counter() - t0))

    def as_dict(self) -> dict:
        out: dict = {}
        for k, v in self.rows:
            out[k] = out.get(k, 0.0) + v
        return out


class Run:
    """What a metric's reader may read."""

    costs = costs

    def __init__(self, **kw):
        self.__dict__.update(kw)

    def per_s_chip(self, per_step: float) -> float:
        """All of the window's work over all of its seconds, per chip."""
        return self.n_steps * per_step / self.window_s / self.chips


def _count_compiles():
    """Counts, from here on, the programs made ready and the persistent
    cache's misses, which are the compilations: jax's monitoring events."""
    from jax import monitoring
    seen = {"programs_loaded": 0, "cache_misses": 0}

    def on_duration(event, duration, **kw):
        # fires for a program built OR read back from the cache
        if event == "/jax/core/compile/backend_compile_duration":
            seen["programs_loaded"] += 1

    def on_event(event, **kw):
        if event == "/jax/compilation_cache/cache_misses":
            seen["cache_misses"] += 1

    monitoring.register_event_duration_secs_listener(on_duration)
    monitoring.register_event_listener(on_event)
    return seen


def find_devices(chips: int, require_tpu: bool):
    """The devices, or None where the cell's chips are not here."""
    import jax
    devs = jax.devices()
    if (require_tpu and devs[0].platform != "tpu") or len(devs) < chips:
        return None
    return devs


def plain_step(system, i: int):
    """Step ``i`` from the seed through the window's own feed and call."""
    return system.step(system.put(system.host_batch(i)))


def first_steps(system) -> tuple:
    """The cell's first steps with the readings the output check compares
    after the window: (losses, observed), still on the device."""
    losses, observed = [], {}
    for i in range(system.check_steps):
        losses.append(plain_step(system, i))
        if i == 0:
            observed["grad"] = system.observe_grad()
            if hasattr(system, "observe_rows"):
                observed["rows"] = system.observe_rows()
    observed["delta"] = system.observe_delta()
    return losses, observed


def host_readings(system, losses, observed) -> dict:
    """What the output check takes of the first steps: ``loss`` per step,
    ``grad`` and ``delta`` per leaf and, where the system has tables,
    ``rows``: the first gradient of each table row by row."""
    import numpy as np
    out = {"loss": [float(x) for x in losses[: system.check_steps]],
           "grad": system.to_host(observed["grad"]),
           "delta": system.to_host(observed["delta"])}
    if "rows" in observed:
        out["rows"] = {k: np.asarray(v) for k, v in observed["rows"].items()}
    return out


def first_readings(system) -> dict:
    """``first_steps`` read back to the host."""
    import jax
    losses, observed = first_steps(system)
    jax.block_until_ready((losses, observed))
    return host_readings(system, losses, observed)


def percentile(values, q: float) -> float:
    """Nearest-rank percentile of all values."""
    s = sorted(values)
    return s[min(len(s) - 1, max(0, math.ceil(q / 100.0 * len(s)) - 1))]


def run_cell(cell_name: str, seed: int, seconds: float, trace: bool, *,
             t_start: float | None = None, require_tpu: bool = True,
             wrap_system=None, root: str = spec.ROOT,
             out=None, err=None) -> int:
    out, err = out or sys.stdout, err or sys.stderr
    t_start = t_start or process_start_time()
    phases = Phases(t_start)
    cell = spec.load_cell(cell_name, root)
    readers = {m["name"]: spec.load_reader(m["name"], cell.bench_dir)
               for m in (cell.per_layer if trace else cell.end_to_end)
               if m["name"] != "setup_s"}

    with phases("import_jax"):
        import jax
        from minips_tpu.utils.compile_cache import enable_compile_cache
        cache_dir = enable_compile_cache()
        compiles = _count_compiles()
    with phases("backend"):
        devs = find_devices(cell.chips, require_tpu)
        if devs is None:
            print(f"bench: cell {cell_name} needs {cell.chips} TPU chip(s); "
                  f"JAX found {jax.devices()}", file=err)
            return NO_CHIP_RC
        kind = devs[0].device_kind
        pk = peaks.peaks_for(kind) if devs[0].platform == "tpu" else None

    system = spec.load_system(cell.config["system"]).build(cell, seed,
                                                           phases)
    if wrap_system is not None:
        system = wrap_system(system)
    mix = cell.traffic
    annotate = jax.profiler.TraceAnnotation

    # ---- the cell's first steps, through the window's own call and feed
    with phases("first_steps"):
        losses, observed = first_steps(system)
        jax.block_until_ready((losses, observed))
    with phases("warmup"):
        for i in range(system.check_steps,
                       system.check_steps + int(mix["warmup_steps"])):
            losses.append(plain_step(system, i))
        jax.block_until_ready(losses)
    compiles_in_setup = dict(compiles)
    setup_s = time.time() - t_start

    # ---- the measured window
    spans = {k: [] for k in ("next_batch", "device_put", "dispatch",
                             "wait")}
    step_s: list = []
    clock = time.perf_counter

    def one_step(i: int) -> None:
        t0 = clock()
        with annotate("bench.next_batch"):
            hb = system.host_batch(i)
        t1 = clock()
        with annotate("bench.device_put"):
            db = system.put(hb)
        t2 = clock()
        with annotate("bench.dispatch"):
            loss = system.step(db)
        t3 = clock()
        with annotate("bench.wait"):
            loss.block_until_ready()
        t4 = clock()
        losses.append(loss)
        step_s.append(t4 - t0)
        for k, v in zip(spans, (t1 - t0, t2 - t1, t3 - t2, t4 - t3)):
            spans[k].append(v)

    first = len(losses)
    trace_s = float(mix["trace_seconds"]) if trace else 0.0
    w0 = clock()
    while True:
        one_step(len(losses))
        if clock() - w0 >= seconds - trace_s:
            break
    traced_steps, trace_summary, tr = 0, None, None
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        plain_s, plain_steps = clock() - w0, len(step_s)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0     # spans come from TraceAnnotation
        jax.profiler.start_trace(TRACE_DIR, profiler_options=options)
        try:
            with annotate("bench.window"):
                t0 = clock()
                while clock() - t0 < trace_s:
                    one_step(len(losses))
                    traced_steps += 1
        finally:
            jax.profiler.stop_trace()
        window_s, n_steps = plain_s, plain_steps
    else:
        window_s, n_steps = clock() - w0, len(step_s)
    compiles_in_window = {k: compiles[k] - compiles_in_setup[k]
                          for k in compiles}

    mem_peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in devs[: cell.chips])
    # ---- the loss over a fixed range of steps from the seed
    loss_steps = [int(x) for x in cell.workload["loss_steps"]]
    while len(losses) < loss_steps[1]:
        losses.append(plain_step(system, len(losses)))
    losses = [float(x) for x in losses]
    window_losses = losses[first: first + len(step_s)]
    failed = sum(1 for x in window_losses if not math.isfinite(x))
    info = system.info()

    if trace:
        path = tracelib.latest_xplane(TRACE_DIR)
        tr = tracelib.events_from_xplane(path)
        trace_summary = tracelib.summarize(tr)
        if os.environ.get("BENCH_KEEP_TRACE") != "1":
            shutil.rmtree(TRACE_DIR, ignore_errors=True)

    # ---- the output check, once the program's state is freed
    prog = host_readings(system, losses, observed)
    system.free()
    t_ref = time.perf_counter()
    try:
        ref = system.reference()
    except Exception as e:      # the result line must still be printed
        print(f"bench: the reference failed: {type(e).__name__}: {e}",
              file=err)
        ref = {"loss": [], "grad": {}, "delta": {}}
    ref_s = time.perf_counter() - t_ref
    correct, rows = check.decide(prog, ref, cell.workload["limits"])
    correct = correct and failed == 0

    run = Run(cell=cell, seed=seed, chips=cell.chips, device_kind=kind,
              peaks=pk, setup_s=setup_s, window_s=window_s, n_steps=n_steps,
              step_s=step_s[:n_steps], spans={k: v[:n_steps]
                                              for k, v in spans.items()},
              samples_per_step=system.samples_per_step,
              tokens_per_step=system.tokens_per_step, losses=losses,
              loss_steps=loss_steps, trace=tr, trace_summary=trace_summary,
              traced_steps=traced_steps, memory_peak_bytes=mem_peak,
              config=cell.config, traffic=mix, info=info,
              percentile=percentile)
    metrics = {}
    units = {m["name"]: m["unit"]
             for m in cell.end_to_end + cell.per_layer}
    if not trace:
        metrics["setup_s"] = {"value": setup_s, "unit": "s"}
    for name, read in readers.items():
        value = read(run)
        if value is not None:
            metrics[name] = {"value": float(value), "unit": units[name]}

    device = {"platform": devs[0].platform, "kind": kind,
              "count": cell.chips, "memory_peak_bytes": int(mem_peak)}
    result = {"correct": bool(correct), "attempted": n_steps,
              "failed": failed, "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = trace_summary["busy_s"]
        device["window_s"] = trace_summary["window_s"]
        result["breakdown"] = {
            "device_ops": trace_summary["device_ops"],
            "idle_gaps": trace_summary["idle_gaps"]}
    result["setup_phases_s"] = phases.as_dict()
    result["compiles"] = {"setup": compiles_in_setup,
                          "window": compiles_in_window,
                          "cache_dir": cache_dir}
    result["reference_s"] = ref_s
    # for a look at a run that reads far off: where its time went
    timed = step_s[:n_steps]
    result["steps_ms"] = {
        "p50": 1e3 * percentile(timed, 50),
        "slowest": [[i, 1e3 * timed[i]] for i in sorted(
            range(len(timed)), key=timed.__getitem__)[-3:][::-1]]}
    result["losses_head"] = losses[:64]
    result["check"] = check.rows_for_result(rows)

    for k, v in phases.rows:
        print(f"setup phase {k}: {v:.3f} s", file=err)
    print(f"setup_s {setup_s:.3f}; compiles in set-up "
          f"{compiles_in_setup}, in window {compiles_in_window}; "
          f"reference {ref_s:.2f} s", file=err)
    check.print_rows(rows, correct, err)
    print(json.dumps(result), file=out, flush=True)
    return 0
