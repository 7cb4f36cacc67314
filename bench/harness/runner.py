"""One run of one cell: set-up, the measured window (traced or not), the
program's memory peak, then the comparison with the plain reference, and
the result line."""

from __future__ import annotations

import gc
import importlib
import json
import os
import shutil
import sys
import time

from harness import device as dev
from harness import spec

TRACE_DIR = os.path.join(spec.ROOT, ".bench_trace")
RUNS_DIR = os.path.join(spec.ROOT, ".bench_runs")


def _driver(cell, seed: int, control: str | None):
    module = importlib.import_module(
        "harness.drivers." + cell.traffic["driver"])
    return module.Driver(cell, seed, control=control)


def enable_compile_cache() -> str:
    """The program's persistent compilation cache (its fixed directory in
    the checkout, or `JAX_COMPILATION_CACHE_DIR`), keeping every
    executable however fast it compiled."""
    import jax

    from repro.launch import compile_cache

    path = compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path


def layer_metrics(cell, ctx: dict) -> dict:
    out = {}
    for m in cell.per_layer:
        value = spec.metric_reader(m["name"])(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def run(cell, seed: int, seconds: float, trace: bool, *, t_start: float,
        control: str | None = None, require_device: bool = True,
        cache: bool = True, runs_dir: str = RUNS_DIR,
        log=sys.stderr) -> dict:
    """The result line of one run. `require_device`, `cache` and `runs_dir`
    let the harness's own tests drive a run on the CPU without touching the
    checkout."""
    import jax

    from harness.counters import CompileCounter

    device = dev.describe(cell.chips, require=require_device)
    cache_dir = enable_compile_cache() if cache else None
    compiles = CompileCounter()
    print(f"[device] {json.dumps(device)} compile_cache={cache_dir}",
          file=log, flush=True)
    driver = _driver(cell, seed, control)
    setup_info = driver.setup(compiles)
    # Set-up's long-lived objects (the pooled requests among them) leave the
    # collector's generations, so that its full passes inside the window
    # scan only what the window allocates.
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - t_start
    warm = compiles.snapshot()
    print(f"[setup] {json.dumps(dict(setup_info, setup_s=setup_s, **warm))}",
          file=log, flush=True)

    trace_dir = os.path.join(TRACE_DIR, cell.name)
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        # Host annotations and device ops; no Python function tracer,
        # which would slow the host-bound window it measures.
        jax.profiler.start_trace(trace_dir, profiler_options=_trace_options())
    before = compiles.lowered
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            driver.window(seconds)
    finally:
        if trace:
            jax.profiler.stop_trace()
    in_window = compiles.lowered - before
    memory_peak = dev.memory_peak_bytes(cell.chips)
    driver.release()
    checks = driver.checks()
    record = dict(driver.record(), window_rates=driver.end_to_end(),
                  compiles_in_window=in_window,
                  setup_s=setup_s, warm_executables=warm)

    device = dict(device, memory_peak_bytes=memory_peak)
    result: dict = {"correct": all(v <= lim for _, v, lim in checks),
                    "attempted": int(driver.attempted),
                    "failed": int(driver.failed)}
    if trace:
        from harness import trace as tr

        red = tr.reduce_file(tr.find_xplane(trace_dir))
        ctx = dict(driver.layer_counters(), compiles_in_window=in_window,
                   trace=red, peaks=_peaks(device), config=cell.config)
        metrics = layer_metrics(cell, ctx)
        device.update(busy_s=red.busy_s, window_s=red.window_s)
        result["metrics"] = metrics
        result["device"] = device
        result["breakdown"] = tr.breakdown(red)
        shutil.rmtree(trace_dir, ignore_errors=True)
    else:
        e2e = dict(driver.end_to_end(), setup_s=setup_s)
        result["metrics"] = {m["name"]: {"value": float(e2e[m["name"]]),
                                         "unit": m["unit"]}
                             for m in cell.end_to_end if m["name"] in e2e}
        result["device"] = device
    result["checks"] = {name: {"value": v, "limit": lim}
                        for name, v, lim in checks}

    _write_run_file(runs_dir, cell.name, seed, trace, control, record,
                    result)
    print(f"[run] {json.dumps(record, default=float)}", file=log, flush=True)
    for name, v, lim in checks:
        print(f"check {name} {v!r} limit {lim!r} "
              f"{'ok' if v <= lim else 'FAIL'}", file=log, flush=True)
    return result


def _trace_options():
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    return options


def _peaks(device: dict) -> dict | None:
    try:
        return dev.peaks(device["kind"])
    except KeyError:
        return None


def _write_run_file(runs_dir, name, seed, trace, control, record,
                    result) -> None:
    os.makedirs(runs_dir, exist_ok=True)
    tag = f"{name}.seed{seed}.trace{int(trace)}" + (
        f".control-{control}" if control else "")
    with open(os.path.join(runs_dir, tag + ".json"), "w") as f:
        json.dump({"record": record, "result": result}, f, default=float,
                  indent=1)
