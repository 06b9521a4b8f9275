"""Scenario-library smoke: run every named scenario end to end.

One row per :mod:`repro.fabric.scenario.library` entry — backend, tenant
count, and the headline per-tenant metric — so CI catches a
library scenario that stopped validating, stopped running, or lost its
failure-mode signal. All entries run at test scale (seconds each).

``--artifacts DIR`` (see ``benchmarks.run``) additionally persists the
smoke table as ``scenarios.csv`` and every library entry's seeded
declarative form as ``BENCH_scenarios.json`` — the exact inputs a later
run (or an external what-if study) needs to reproduce the numbers.
"""
from __future__ import annotations

import json
import os
from typing import List

from repro.fabric.scenario import Scenario, library


_ROWS: List[str] = []


def rows() -> List[str]:
    # memoized: the printed table and write_artifacts() share one run of
    # the library
    if _ROWS:
        return _ROWS
    lines = ["scenario,backend,tenants,headline"]
    for name in library.names():
        scn = library.build(name)
        # the declarative form is part of the contract: every library
        # entry must survive its own JSON round trip
        assert Scenario.from_json(scn.to_json()).to_dict() == scn.to_dict()
        res = scn.run()
        diags = res.diagnostics()
        parts = []
        for tname, d in diags.items():
            if d["kind"] == "inference":
                parts.append(f"{tname}: p99={d['p99_latency_s'] * 1e3:.0f}ms"
                             f" slo={d['slo_attainment'] * 100:.0f}%")
            else:
                parts.append(f"{tname}: {d['mean_step_s'] * 1e3:.0f}ms/step"
                             f" cv={d['cv']:.3f}")
        lines.append(f"{name},{res.kind},{len(diags)},"
                     + " | ".join(parts))
    _ROWS.extend(lines)
    return _ROWS


def write_artifacts(outdir: str) -> List[str]:
    """Persist the smoke table (CSV) and the seeded scenario library
    (JSON dict forms, base_seed included) as CI artifacts.

    ``BENCH_scenarios.json`` is also refreshed at the repository root,
    where it is *tracked in git*: the declarative inputs behind the
    benchmark numbers diff in review alongside the code that changes
    them, and a stale copy (a library edit without a bench run) shows up
    as an uncommitted change in CI."""
    csv_path = os.path.join(outdir, "scenarios.csv")
    with open(csv_path, "w") as f:
        f.write("\n".join(rows()) + "\n")
    payload = json.dumps({name: library.build(name).to_dict()
                          for name in library.names()}, indent=1,
                         sort_keys=True) + "\n"
    json_path = os.path.join(outdir, "BENCH_scenarios.json")
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    tracked_path = os.path.join(repo_root, "BENCH_scenarios.json")
    written = []
    for path in dict.fromkeys(
            (os.path.abspath(json_path), tracked_path)):
        with open(path, "w") as f:
            f.write(payload)
        written.append(path)
    return [csv_path] + written


def main() -> None:
    for ln in rows():
        print(ln)


if __name__ == "__main__":
    main()
