"""One run of one cell: set-up, the measured window, the check.

Set-up builds the cell's base scenario and runs one warm-up sweep on a
seed no window sweep uses, which compiles (or loads from the persistent
cache) every runner the cell's sweeps call, the long-ring rerun
included. The window then runs whole sweeps back to back until they
have taken ``seconds``; the sweep running at that moment is finished
and counted. Each sweep is a new study: the base scenario with a fresh
``base_seed`` (:func:`bench.cells.base_seed`), rebuilt as a
``ScenarioGrid`` over the mix's axes and run with
``ScenarioGrid.run(backend=...)``. The program's caches are left as a
user would have them.

In the traced run the profiler records the window's first sweeps, up to
``TRACE_SECONDS``, with the benchmark's host spans around the program's
layers (:mod:`bench.spans`); the per-layer readers read those sweeps.

Once the window has closed and the device's peak memory is read, a
sample of the variants the window returned, drawn from the seed, is
compared with the plain reference (:mod:`bench.check`).
"""
from __future__ import annotations

import dataclasses
import importlib
import math
import random
import statistics
import sys
import time
from typing import Callable, Dict, List, Optional

import numpy as np

from bench import cells, check, trace as tr
from bench.spans import Spans, annotate

TRACE_SECONDS = 3.0


@dataclasses.dataclass
class Context:
    """What a per-layer reader reads: the host spans and runner names of
    the traced sweeps, their variants and tenant-iterations, the trace
    and its window."""
    spans: Spans
    variants: int
    tenant_iters: int
    trace: Optional[tr.Trace]
    window: Optional[tuple]


class CompileCounter:
    """Programs compiled, or loaded from the persistent cache, while
    ``active``."""

    def __init__(self):
        from jax import monitoring
        from jax._src import dispatch
        self.count = 0
        self.active = False
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)
        self._backend = getattr(dispatch, "BACKEND_COMPILE_EVENT",
                                "/jax/core/compile/backend_compile_duration")

    def _duration(self, event, seconds, **kw):
        if self.active and event == self._backend:
            self.count += 1

    def _event(self, event, **kw):
        if self.active and event == "/jax/compilation_cache/cache_hits":
            self.count += 1


def _count_failed(results, iters_reported: int):
    """``(attempted, failed)`` over one sweep's variants: a variant fails
    when a tenant's series is missing, short or not finite."""
    failed = 0
    for _, res in results:
        for job in res.scenario.jobs:
            s = res.series(job.name)
            if len(s) != iters_reported or not math.isfinite(sum(s)):
                failed += 1
                break
    return len(results), failed


def run(name: str, seed: int, seconds: float, traced: bool,
        device, t_start: float,
        shrink: Optional[Callable[[dict, dict], tuple]] = None,
        trace_dir: Optional[str] = None) -> dict:
    """Run cell ``name`` once; returns the result line's object (its
    ``checks`` last). ``device`` is the JAX device the cell runs on;
    ``t_start`` the host clock at process start. ``shrink`` maps the
    scenario dict and the mix to smaller ones (tests only)."""
    import jax
    from repro.fabric.scenario import Scenario, ScenarioGrid

    spec = cells.cell(name)
    scenario = spec["config_data"]["scenario"]
    traffic = spec["traffic_data"]
    if shrink is not None:
        scenario, traffic = shrink(scenario, traffic)
    limits = spec["limits"]
    backend = traffic["backend"]
    base = Scenario.from_dict(scenario)
    n_var = math.prod(len(v) for v in
                      cells.sweep_axes(traffic, 0).values())
    per_sweep = cells.tenant_iters(scenario, n_var)
    reported = scenario["iters"] - scenario["warmup"]

    def sweep(i: int):
        bs = cells.base_seed(seed, i, traffic["seeds"])
        with annotate("sweep"):
            with annotate("grid"):
                grid = ScenarioGrid(base.replace(base_seed=bs),
                                    cells.sweep_axes(traffic, bs))
            return bs, grid.run(backend=backend)

    compiles = CompileCounter()
    sweep(-1)                                   # warm-up: compiles

    spans = Spans()
    times: List[float] = []                     # each sweep's wall time
    attempted = failed = 0
    kept: List[tuple] = []
    traced_sweeps = 0
    tracing = False
    if traced:
        spans.install()
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0         # host spans, no per-call
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        tracing = True
    compiles.active = True
    setup_s = time.perf_counter() - t_start
    # The window is the sweeps back to back: the harness's own
    # bookkeeping between them (counting failures, keeping the variants
    # to check) is left out of its time.
    i = 0
    while sum(times) < seconds:
        t0 = time.perf_counter()
        try:
            bs, results = sweep(i)
        except Exception as e:                  # a sweep that raised
            times.append(time.perf_counter() - t0)
            attempted += n_var
            failed += n_var
            print(f"sweep {i} raised {type(e).__name__}: {e}",
                  file=sys.stderr, flush=True)
            results = None
        else:
            times.append(time.perf_counter() - t0)
            a, f = _count_failed(results, reported)
            attempted += a
            failed += f
        if tracing and sum(times) >= min(TRACE_SECONDS, seconds):
            jax.profiler.stop_trace()
            spans.uninstall()
            tracing = False
            traced_sweeps = len(times)
        if results is not None:                 # candidates for the check
            pick = random.Random(f"{seed}:{i}:pick")
            for k in pick.sample(range(len(results)),
                                 min(limits["checked"], len(results))):
                res = results[k][1]
                got = np.array([res.series(j.name)
                                for j in res.scenario.jobs],
                               dtype=np.float64).T
                kept.append((i, bs, k, results[k][0], got))
        i += 1
    window_s = sum(times)
    compiles.active = False
    if tracing:
        jax.profiler.stop_trace()
        spans.uninstall()
        traced_sweeps = len(times)

    stats = device.memory_stats() or {}
    dev = {"platform": device.platform, "kind": device.device_kind,
           "count": len(jax.devices()),
           "memory_peak_bytes": stats.get("peak_bytes_in_use")}

    out = {"correct": False, "attempted": attempted, "failed": failed}
    if traced:
        metrics, breakdown = _per_layer(spec, spans, traced_sweeps, n_var,
                                        per_sweep, trace_dir)
        trace_window = breakdown.pop("window")
        if trace_window is not None:
            dev["busy_s"] = breakdown.pop("busy_s")
            dev["window_s"] = trace_window[1] - trace_window[0]
        out["metrics"] = metrics
        out["breakdown"] = breakdown
    else:
        metrics = {"tenant_iters_per_s": per_sweep * len(times) / window_s,
                   "setup_s": setup_s}
        if len(times) >= 2:
            metrics["sweep_s_p95"] = statistics.quantiles(
                times, n=20, method="inclusive")[18]
        units = {m["name"]: m["unit"] for m in spec["end_to_end"]}
        out["metrics"] = {k: {"value": v, "unit": units[k]}
                          for k, v in metrics.items() if k in units}
    out["device"] = dev
    out["window"] = {"sweeps": len(times), "seconds": window_s,
                     "tenant_iters_per_sweep": per_sweep,
                     "compiles": compiles.count,
                     "sweep_s_min": min(times), "sweep_s_max": max(times)}

    checks, detail = _check(scenario, traffic, seed, limits, kept, failed)
    out["check_detail"] = detail
    out["correct"] = all(c["value"] <= c["limit"] for c in checks.values())
    out["checks"] = checks
    return out


def _check(scenario, traffic, seed, limits, kept, failed):
    """Compare ``limits["checked"]`` of the kept variants, drawn from the
    seed, with the reference; returns each number the cell's limits name,
    the worst over the variants, beside its limit."""
    rng = random.Random(f"{seed}:check")
    detail = []
    for (i, bs, k, params, got) in rng.sample(
            kept, min(limits["checked"], len(kept))):
        p_ref, scn = cells.variants(scenario, cells.sweep_axes(traffic, bs),
                                    bs)[k]
        if p_ref != params:
            c = {"horizon": 0, "span": 0,
                 **dict.fromkeys(check.NUMBERS, float("inf"))}
        else:
            c = check.compare(scn, got, limits["departure"])
        detail.append(dict(c, sweep=i, variant=k,
                           params=",".join(f"{p.split('.')[-1]}={x}"
                                           for p, x in params.items())))
    checks = {}
    for key, limit in limits["limits"].items():
        value = failed if key == "failed_variants" else \
            max([c[key] for c in detail], default=0.0)
        # JSON has no infinity
        checks[key] = {"value": min(value, sys.float_info.max),
                       "limit": limit}
    return checks, detail


def _per_layer(spec, spans, sweeps, n_var, per_sweep, trace_dir):
    path = tr.find(trace_dir) if trace_dir else None
    trace = tr.load(path) if path else None
    window = trace.window() if trace is not None else None
    ctx = Context(spans=spans, variants=sweeps * n_var,
                  tenant_iters=sweeps * per_sweep, trace=trace,
                  window=window)
    metrics = {}
    for m in spec["per_layer"]:
        value = importlib.import_module("bench.metrics." + m["name"]).read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    breakdown = {"window": window}
    if window is not None:
        ops = trace.ops or trace.modules
        intervals = [(s, e) for _, s, e in ops]
        breakdown["busy_s"] = tr.busy(intervals, window)
        breakdown["device_ops"] = tr.top(tr.op_seconds(ops, window))
        inner = [sp for sp in trace.spans if sp[0] != "sweep"]
        breakdown["idle_gaps"] = tr.top(tr.attribute(
            tr.gaps(intervals, window), inner))
    return metrics, breakdown
