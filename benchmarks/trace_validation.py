"""Trace validation: fit, replay, and calibrate every bundled trace.

One row per bundled trace under ``tests/traces/`` — record count, fitted
congestion, per-tenant predicted-vs-observed mean/p99 relative error and
series correlation from replaying the fit — followed by a calibration
demonstration (ScenarioGrid sweep over congestion parameters, jnp-batched
for static traces) showing the error the sweep recovers over the
uncalibrated fit. The acceptance gates (mean error <= 10%, p99 <= 20%)
are printed per trace so the CI log reads as a pass/fail table.

``--artifacts DIR`` persists ``trace_errors.csv`` (the per-tenant error
report) and ``trace_calibration.csv`` (the per-cell sweep table from
:meth:`repro.fabric.trace.Calibration.to_csv`) for
``actions/upload-artifact``.
"""
from __future__ import annotations

import os
from typing import List

from repro.fabric.trace import (BUNDLED_TRACES, calibrate, fit_trace,
                                load_trace, validate_result)

MEAN_GATE = 0.10
P99_GATE = 0.20

TRACE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "tests", "traces")

_ROWS: List[str] = []
_ERROR_CSV: List[str] = []
_CALIBRATIONS = {}


def _trace_path(name: str) -> str:
    return os.path.join(TRACE_DIR, f"{name}.json")


def rows() -> List[str]:
    # memoized: the printed table and write_artifacts() share one run
    if _ROWS:
        return _ROWS
    lines: List[str] = []
    _ERROR_CSV.append("trace,tenant,kind,n_observed,mean_rel_err,"
                      "p99_rel_err,correlation,gates")
    for name in BUNDLED_TRACES:
        tr = load_trace(_trace_path(name))
        fit = fit_trace(tr)
        val = validate_result(fit.scenario.run(backend="reference"), tr)
        ov = val.overall()
        ok = ov["mean_rel_err"] <= MEAN_GATE and ov["p99_rel_err"] <= P99_GATE
        u = fit.scenario.congestion.u_mean \
            if fit.scenario.congestion is not None else 0.0
        lines.append(
            f"{name}: {len(tr.records)} records, "
            f"u_mean={u:.3f}, mean_err={ov['mean_rel_err'] * 100:.2f}% "
            f"p99_err={ov['p99_rel_err'] * 100:.2f}% "
            f"[{'PASS' if ok else 'FAIL'} gates {MEAN_GATE:.0%}/"
            f"{P99_GATE:.0%}]")
        for note in fit.notes:
            lines.append(f"  note: {note}")
        for tname, tv in sorted(val.tenants.items()):
            lines.append(
                f"  {tname} ({tv.kind}): n={tv.n_observed} "
                f"mean {tv.observed_mean * 1e3:.1f}ms -> "
                f"{tv.predicted_mean * 1e3:.1f}ms "
                f"({tv.mean_rel_err * 100:.2f}%), p99 "
                f"{tv.observed_p99 * 1e3:.1f}ms -> "
                f"{tv.predicted_p99 * 1e3:.1f}ms "
                f"({tv.p99_rel_err * 100:.2f}%), r={tv.correlation:.3f}")
            _ERROR_CSV.append(
                f"{name},{tname},{tv.kind},{tv.n_observed},"
                f"{tv.mean_rel_err:.6f},{tv.p99_rel_err:.6f},"
                f"{tv.correlation:.4f},{'pass' if ok else 'fail'}")
        if not ok:
            raise AssertionError(
                f"{name}: replay error outside acceptance gates: {val!r}")
    for name in BUNDLED_TRACES:
        cal = calibrate(_trace_path(name))
        _CALIBRATIONS[name] = cal
        lines.append(
            f"calibrate {name}: backend={cal.backend} "
            f"cells={len(cal.cells)}, score "
            f"{cal.seed_validation.score():.4f} -> "
            f"{cal.best_validation.score():.4f} at {cal.best_params} "
            f"({'improved' if cal.improved else 'seed cell optimal'})")
    _ROWS.extend(lines)
    return _ROWS


def write_artifacts(outdir: str) -> List[str]:
    """Persist the per-tenant error report and the calibration sweep
    tables as CI artifacts."""
    rows()  # ensure the memoized run happened
    err_path = os.path.join(outdir, "trace_errors.csv")
    with open(err_path, "w") as f:
        f.write("\n".join(_ERROR_CSV) + "\n")
    written = [err_path]
    cal_path = os.path.join(outdir, "trace_calibration.csv")
    with open(cal_path, "w") as f:
        for name in BUNDLED_TRACES:
            f.write(f"# {name} (backend={_CALIBRATIONS[name].backend})\n")
            f.write(_CALIBRATIONS[name].to_csv())
    written.append(cal_path)
    return written
