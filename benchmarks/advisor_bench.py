"""Advisor smoke: attribution + what-if recommendations per failure mode.

One block per failure-mode library scenario — the per-tenant bucket
decomposition (which bucket dominates, at what share of the overhead)
followed by the advisor's ranked counterfactuals with their reference-
verified recoveries. CI catches an attribution that stopped ranking the
scenario's namesake bucket first, and an advisor whose top
recommendation stopped recovering the attributed overhead.

``--artifacts DIR`` (see ``benchmarks.run``) additionally persists every
recommendation as ``advisor_recommendations.csv`` — one row per
(scenario, counterfactual) with predicted and verified deltas, so a
what-if study diffs in review alongside the model changes that moved it.
"""
from __future__ import annotations

import csv
import os
from typing import Dict, List, Tuple

from repro.fabric.advisor import Recommendation, attribute
from repro.fabric.scenario import library

# the paper's named failure modes: (library entry, afflicted tenant)
FAILURE_MODES = (
    ("synchronization_amplification", "bsp"),
    ("topology_contention", "primary"),
    ("locality_variance", "job"),
)

_ROWS: List[str] = []
_RECS: List[Tuple[str, Recommendation]] = []

CSV_FIELDS = ("scenario", "action", "bucket", "tenant", "edits",
              "predicted_delta_s", "predicted_recovery",
              "verified_delta_s", "confidence", "backend")


def rows() -> List[str]:
    # memoized: the printed table and write_artifacts() share one sweep
    if _ROWS:
        return _ROWS
    lines = []
    for name, tenant in FAILURE_MODES:
        scn = library.build(name)
        res = scn.run()
        attr = attribute(res)
        recs = res.advise()
        _RECS.extend((name, r) for r in recs)
        ta = attr[tenant]
        b = ta.mean
        lines.append(f"{name} [{tenant}]: overhead "
                     f"{b.overhead_s * 1e3:.2f} ms/step, dominant "
                     f"{b.dominant} ({b.share(b.dominant) * 100:.0f}%),"
                     f" {len(recs)} counterfactuals")
        for r in recs:
            lines.append(f"    {r.summary()}")
    _ROWS.extend(lines)
    return _ROWS


def write_artifacts(outdir: str) -> List[str]:
    """Persist the executed counterfactuals as a CSV artifact."""
    rows()  # ensure the sweep ran (and _RECS is populated)
    csv_path = os.path.join(outdir, "advisor_recommendations.csv")
    with open(csv_path, "w", newline="") as f:
        w = csv.DictWriter(f, fieldnames=CSV_FIELDS)
        w.writeheader()
        for name, rec in _RECS:
            row: Dict[str, object] = {"scenario": name}
            row.update(rec.to_row())
            w.writerow(row)
    return [csv_path]
