"""Benchmark aggregator: one section per paper artifact.

  table1      — paper Table 1 (baseline vs coordination, 5 node counts)
  scaling     — paper Fig. 1/5 (observed vs ideal curves + CVs)
  taxonomy    — paper Fig. 2 / §3.3 (failure-mode attribution)
  multitenant — §3.2/§3.3 co-tenant contention + placement sweeps (engine)
  lifecycle   — event-driven scenarios: arrivals, failure recovery,
                max-min vs offered-bytes fairness (lifecycle engine)
  wfq         — weighted fair sharing: inference-weight sweep (p99 / SLO
                attainment vs training throughput) + scheduler policies
  batching    — continuous-batching sweep: batch size vs p99/throughput
                (single stream vs batch-join fleets at high arrival rate)
  scenarios   — scenario-library smoke: every named scenario end to end
  topology    — ranks vs step cost across fat_tree/rail/multi-pod and
                ecmp_static vs adaptive_spray (sparse-fabric scaling)
  pacing      — vectorized PacingBank vs scalar controllers (before/after)
  speedup     — compiled-schedule engine vs seed per-call loop wall-clock
  backend     — batched jnp grid sweep vs sequential reference engine
                (kernel-registry backend, targets >= 50x warm)
  kernels     — kernel micro-benchmarks: substrate (attention/rmsnorm/
                wkv6/mamba) + the fabric registry hot paths (reference
                vs jnp vs pallas-interpret at the dense-sweep shape)
  trace       — bundled-trace validation: fit + replay error report
                (mean/p99 gates) and the congestion calibration sweep
  roofline    — per-cell roofline terms from the dry-run artifacts

Run everything: ``PYTHONPATH=src python -m benchmarks.run``
One section:    ``PYTHONPATH=src python -m benchmarks.run --only table1``
CI artifacts:   ``... --only batching --artifacts bench-artifacts`` writes
the section's CSV/JSON files (ScenarioGrid sweeps, the seeded scenario
library) into the directory for ``actions/upload-artifact`` to keep.
"""
from __future__ import annotations

import argparse
import os
import sys
import time


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--only", default=None,
                    choices=["table1", "scaling", "taxonomy", "multitenant",
                             "lifecycle", "wfq", "batching", "scenarios",
                             "topology", "pacing", "speedup", "backend",
                             "kernels", "trace", "advisor", "roofline"])
    ap.add_argument("--artifacts", default=None, metavar="DIR",
                    help="write sections' CSV/JSON artifacts into DIR")
    args = ap.parse_args()
    from repro.fabric.backend import use_compile_cache
    use_compile_cache()

    sections = []
    artifact_writers = []
    if args.only in (None, "table1"):
        from benchmarks import table1_coordination
        sections.append(("table1_coordination (paper Table 1)",
                         table1_coordination.rows))
    if args.only in (None, "scaling"):
        from benchmarks import scaling_curve
        sections.append(("scaling_curve (paper Fig. 1/5)",
                         lambda: scaling_curve.rows()
                         + scaling_curve.ascii_plot()))
    if args.only in (None, "taxonomy"):
        from benchmarks import bottleneck_taxonomy
        sections.append(("bottleneck_taxonomy (paper Fig. 2 / §3.3)",
                         bottleneck_taxonomy.rows))
    if args.only in (None, "multitenant"):
        from benchmarks import multitenant
        sections.append(("multitenant (paper §3.2/§3.3, shared-fabric "
                         "engine)", multitenant.rows))
    if args.only in (None, "lifecycle"):
        from benchmarks import lifecycle
        sections.append(("lifecycle (event-driven tenant scenarios)",
                         lifecycle.rows))
    if args.only in (None, "wfq"):
        from benchmarks import wfq_sweep
        sections.append(("wfq_sweep (weighted sharing + scheduler "
                         "policies)", wfq_sweep.rows))
    if args.only in (None, "batching"):
        from benchmarks import batching
        sections.append(("batching (continuous batching vs single stream)",
                         batching.rows))
        artifact_writers.append(batching.write_artifacts)
    if args.only in (None, "scenarios"):
        from benchmarks import scenarios
        sections.append(("scenarios (named scenario library smoke)",
                         scenarios.rows))
        artifact_writers.append(scenarios.write_artifacts)
    if args.only in (None, "topology"):
        from benchmarks import topology_bench
        sections.append(("topology_bench (sparse fabrics: ranks vs step "
                         "cost, ecmp vs spray)", topology_bench.rows))
        artifact_writers.append(topology_bench.write_artifacts)
    if args.only in (None, "pacing"):
        from benchmarks import pacing_bench
        sections.append(("pacing (vectorized bank vs scalar controllers)",
                         pacing_bench.rows))
    if args.only in (None, "speedup"):
        from benchmarks import engine_speedup
        sections.append(("engine_speedup (compiled schedules vs seed loop)",
                         engine_speedup.rows))
    if args.only in (None, "backend"):
        from benchmarks import backend_bench
        sections.append(("backend_bench (batched jnp sweep vs sequential "
                         "reference)", backend_bench.rows))
        artifact_writers.append(backend_bench.write_artifacts)
    if args.only in (None, "kernels"):
        from benchmarks import kernel_bench
        sections.append(("kernel_bench (substrate + fabric registry)",
                         kernel_bench.rows))
        artifact_writers.append(kernel_bench.write_artifacts)
    if args.only in (None, "trace"):
        from benchmarks import trace_validation
        sections.append(("trace_validation (bundled-trace fit + replay "
                         "gates + calibration)", trace_validation.rows))
        artifact_writers.append(trace_validation.write_artifacts)
    if args.only in (None, "advisor"):
        from benchmarks import advisor_bench
        sections.append(("advisor (bottleneck attribution + what-if "
                         "recommendations)", advisor_bench.rows))
        artifact_writers.append(advisor_bench.write_artifacts)
    if args.only in (None, "roofline"):
        from benchmarks import roofline_table
        sections.append(("roofline_table single-pod (assignment)",
                         lambda: roofline_table.rows("single")))
        sections.append(("roofline_table multi-pod (assignment)",
                         lambda: roofline_table.rows("multi")))

    failures = 0
    for title, fn in sections:
        print(f"\n=== {title} ===")
        t0 = time.time()
        try:
            for ln in fn():
                print(ln)
            print(f"--- done in {time.time() - t0:.1f}s")
        except Exception as e:  # noqa: BLE001
            failures += 1
            print(f"--- FAILED: {type(e).__name__}: {e}")
    if args.artifacts and not failures:
        os.makedirs(args.artifacts, exist_ok=True)
        for write in artifact_writers:
            for path in write(args.artifacts):
                print(f"wrote {path}")
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
