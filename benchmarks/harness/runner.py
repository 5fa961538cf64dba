"""One process, one cell, one run: set-up, the measured window, with
`--trace 1` a traced sub-window after it, the check of the outputs, and
the result line.

A mode (benchmarks/modes/<mode>.py) drives the program for one kind of
traffic and provides

    setup(ctx) -> state              everything before the window
    window(ctx, state, seconds)      -> {"seconds", "attempted", "failed",
                                         "units": {...}}
    traced(ctx, state)               -> {"units": {...}, "counters": {...}}
    check(ctx, state) -> [(name, ok, detail)]

and the readers under end_to_end/ and layer_metrics/ turn the Evidence
into the metrics BENCHMARK.json names for the cell.
"""
from __future__ import annotations

import dataclasses
import json
import os
import shutil
import sys
import traceback

from . import clock, device, jaxmon, loader, program, result, work, xplane

TRACE_DIR = os.path.join(loader.ROOT, ".bench_trace")


@dataclasses.dataclass
class Context:
    cell: dict
    config: dict
    traffic: dict
    seed: int
    spans: clock.Spans
    load = staticmethod(loader.load_module)

    def stage(self, name: str):
        """A set-up stage; its seconds go on an earlier line."""
        return self.spans.span("setup:" + name)

    def span(self, name: str):
        return self.spans.span(name)

    @staticmethod
    def say(msg: str) -> None:
        print(msg, flush=True)


@dataclasses.dataclass
class Evidence:
    """What a metric's reader may read."""
    cell: dict
    config: dict
    traffic: dict
    setup_s: float
    stages: dict            # set-up stage -> seconds
    window: dict            # seconds, attempted, failed, units, compiles
    traced: dict | None     # units, counters of the traced sub-window
    trace: xplane.TraceSummary | None
    spans: clock.Spans
    device: dict            # platform, kind, count, memory_peak_bytes
    peaks: dict | None      # the device kind's row of peaks.json
    artifacts: dict         # what the mode kept for readers (the model)
    work = work


def _merge(into: dict, over: dict) -> None:
    for key, value in over.items():
        if isinstance(value, dict) and isinstance(into.get(key), dict):
            _merge(into[key], value)
        else:
            into[key] = value


def _drain() -> None:
    """Wait until the device has nothing queued."""
    import jax
    import jax.numpy as jnp
    for d in jax.devices():
        jax.block_until_ready(jax.device_put(jnp.zeros((), jnp.int32), d) + 1)


def _traced_subwindow(ctx: Context, mode, state, name: str):
    """Run mode.traced under the profiler; (its counts, TraceSummary)."""
    import jax
    trace_dir = os.path.join(TRACE_DIR, name)
    shutil.rmtree(trace_dir, ignore_errors=True)
    os.makedirs(trace_dir)
    _drain()
    # the device's ops and the benchmark's spans; no Python call stacks,
    # which slow the host they are meant to watch
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    ctx.spans.annotate = True
    try:
        with ctx.span(xplane.WINDOW_SPAN):
            traced = mode.traced(ctx, state)
    finally:
        ctx.spans.annotate = False
        jax.profiler.stop_trace()
    return traced, xplane.reduce(xplane.load(xplane.find_xplane(trace_dir)))


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             t_start: float, require_tpu: bool = True,
             overrides: dict | None = None) -> int:
    """Run the cell and print its lines; the exit code. `require_tpu` and
    `overrides` ({"config": {...}, "traffic": {...}}, merged over the
    files) exist for the rehearsal in tests/: the measuring command never
    passes them."""
    bench = loader.load_benchmark()
    cell = loader.find_cell(bench, workload)
    config = loader.load_config(bench, cell["config"])
    traffic = loader.load_traffic(cell["traffic"])
    overrides = overrides or {}
    _merge(config, overrides.get("config", {}))
    _merge(traffic, overrides.get("traffic", {}))
    mode = loader.load_module("modes", traffic["mode"])

    found = (device.require(cell["chips"]) if require_tpu
             else device.describe())
    peaks = device.peaks_for(found["kind"]) if require_tpu else None
    compiles = jaxmon.install()
    cache_dir = program.ensure_compile_cache()
    ctx = Context(cell, config, traffic, seed, clock.Spans())
    ctx.say(f"cell {workload}: config={cell['config']} "
            f"traffic={cell['traffic']} seed={seed} seconds={seconds} "
            f"trace={int(trace)} device={found} cache={cache_dir}")

    state = mode.setup(ctx)
    _drain()
    setup_s = clock.now() - t_start
    stages = {n[len("setup:"):]: t1 - t0 for n, t0, t1 in ctx.spans.events
              if n.startswith("setup:")}
    at_setup = compiles.copy()
    ctx.say(f"setup_s={setup_s:.3f} stages="
            + " ".join(f"{k}={v:.3f}" for k, v in stages.items())
            + f" executables={at_setup.executables} "
            f"(persistent cache: {at_setup.cache_hits} hits, "
            f"{at_setup.cache_misses} misses; {at_setup.executable_s:.1f}s)")

    window = mode.window(ctx, state, seconds)
    window["compiles"] = compiles.executables - at_setup.executables
    ctx.say(f"window: {window}")

    traced = summary = None
    if trace:
        traced, summary = _traced_subwindow(ctx, mode, state, workload)
        ctx.say(f"traced sub-window: {traced}")
    found["memory_peak_bytes"] = device.memory_peak_bytes()

    checks = [("window_compiles", window["compiles"] == 0,
               f"{window['compiles']} executables built or fetched inside "
               "the window"),
              ("none_failed", window["failed"] == 0,
               f"{window['failed']} of {window['attempted']} failed")]
    if trace and require_tpu:
        checks.append(("device_trace", summary is not None,
                       "an op ran on the device inside the traced "
                       "sub-window"))
    try:
        checks += mode.check(ctx, state)
    except Exception:
        traceback.print_exc()
        checks.append(("check_ran", False, "the check raised"))
    for name, ok, detail in checks:
        ctx.say(f"check {name}: {'ok' if ok else 'FAILED'} ({detail})")
    ctx.say(f"compile manager: {program.compile_counters()}")

    ev = Evidence(cell, config, traffic, setup_s, stages, window, traced,
                  summary, ctx.spans, found, peaks,
                  getattr(state, "artifacts", {}))
    both = {section: result.metric_values(
        loader.metrics_of(bench, section, workload), ev)
        for section in (("end_to_end", "per_layer") if trace
                        else ("end_to_end",))}
    breakdown = None
    if trace:
        # the traced run measured its window untraced first: say what it
        # saw, on an earlier line; its result line carries the layers
        ctx.say("end_to_end (not this line's metrics): "
                + json.dumps(both["end_to_end"]))
        if summary is not None:
            found["busy_s"], found["window_s"] = \
                summary.busy_s, summary.window_s
            breakdown = {"device_ops": summary.top_ops(10),
                         "idle_gaps": summary.top_gaps(5)}
    sys.stdout.flush()
    print(result.result_line(
        correct=all(ok for _, ok, _ in checks),
        attempted=window["attempted"], failed=window["failed"],
        metrics=both["per_layer" if trace else "end_to_end"],
        device=found, breakdown=breakdown), flush=True)
    return 0
