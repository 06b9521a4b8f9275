"""Topology-scale table: ranks vs materialized links across fabric kinds.

One row per (topology kind, rank count, routing policy) cell — the
materialized link count and the tenant's simulated mean step time —
showing that the sparse kinds (``rail_optimized``, ``multi_pod``)
materialize links in proportion to the tenants' footprint while the dense
``fat_tree`` table grows with the fabric, and what ``adaptive_spray``
buys over ``ecmp_static`` on multi-pod fabrics with parallel global links.

``--artifacts DIR`` persists the table as ``topology.csv``.
"""
from __future__ import annotations

import os
import statistics
from typing import List

from repro.fabric.engine import JobSpec
from repro.fabric.scenario import Policies, Scenario, TopologySpec

# every cell runs the same modest two-tenant population so the columns
# compare fabrics, not workloads; tenants straddle locality boundaries
_ITERS = 30
_WARMUP = 5


def _spec(kind: str, n_ranks: int) -> TopologySpec:
    if kind == "fat_tree":
        return TopologySpec(kind="fat_tree", n_nodes=n_ranks,
                            nodes_per_leaf=8)
    if kind == "rail_optimized":
        return TopologySpec(kind="rail_optimized", n_nodes=n_ranks,
                            gpus_per_node=8)
    return TopologySpec(kind="multi_pod", n_pods=max(2, n_ranks // 8192),
                        ranks_per_pod=min(n_ranks // 2, 8192),
                        nodes_per_leaf=8, inter_pod_links=4)


_GRID = [
    ("fat_tree", "ecmp_static", (64, 512, 4096)),
    ("rail_optimized", "ecmp_static", (64, 512, 4096)),
    ("multi_pod", "ecmp_static", (4096, 16384, 131072)),
    ("multi_pod", "adaptive_spray", (4096, 16384, 131072)),
]

_ROWS: List[str] = []


def rows() -> List[str]:
    if _ROWS:
        return _ROWS
    lines = ["kind,routing,ranks,links,mean_step_s"]
    for kind, routing, rank_counts in _GRID:
        for n_ranks in rank_counts:
            spec = _spec(kind, n_ranks)
            tenant = min(256, n_ranks // 4)
            if kind == "multi_pod":
                # straddle the pod boundary so inter-pod routing matters
                rpp = spec.ranks_per_pod
                h = tenant // 2
                jobs = (JobSpec("a", tenant,
                                nodes=tuple(range(rpp - h, rpp + h))),
                        JobSpec("b", tenant,
                                nodes=tuple(range(rpp - tenant, rpp - h))
                                + tuple(range(rpp + h, rpp + tenant)),
                                grad_bytes=2e9))
            else:
                jobs = (JobSpec("a", tenant, placement="compact"),
                        JobSpec("b", tenant, placement="compact",
                                grad_bytes=2e9))
            scn = Scenario(
                name=f"bench_{kind}_{n_ranks}",
                topology=spec,
                jobs=jobs,
                policies=Policies(routing=routing),
                iters=_ITERS, warmup=_WARMUP)
            res = scn.run()
            mean_step = statistics.fmean(res.series("a"))
            lines.append(
                f"{kind},{routing},{spec.n_ranks},{len(res.topo.links)},"
                f"{mean_step:.6f}")
    _ROWS.extend(lines)
    return _ROWS


def write_artifacts(outdir: str) -> List[str]:
    path = os.path.join(outdir, "topology.csv")
    with open(path, "w") as f:
        f.write("\n".join(rows()) + "\n")
    return [path]


def main() -> None:
    for ln in rows():
        print(ln)


if __name__ == "__main__":
    main()
