"""What a cell is made of, found by name: ``BENCHMARK.json`` names the
cell's configuration and traffic mix; the configuration is
``bench/configs/<config>.json``, the mix ``bench/traffic/<traffic>.json``
and the correctness limits ``bench/limits/<cell>.json``. A later cell,
mix or limit set is a new file, not an edit.

A configuration file holds the deployment as ``Scenario.to_dict()``
JSON under ``scenario``, with ``source``, ``assumed``, ``reduced`` and the
``precision`` the batched runner states. A traffic file holds the grid
``axes`` swept over it, the ``backend`` the sweep asks for, how each
sweep's ``base_seed`` is drawn (``seeds``) and, optionally, a
``seed_axis``: one more axis whose values are seeds drawn from the
sweep's own (``count`` of them, ``stride`` apart).
"""
from __future__ import annotations

import copy
import hashlib
import itertools
import json
import os
from typing import Dict, List, Tuple

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.join(ROOT, "bench")


def _load(*parts) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def benchmark() -> dict:
    return _load(ROOT, "BENCHMARK.json")


def cell(name: str) -> dict:
    """The cell's ``workloads`` entry with its configuration, traffic and
    limits loaded under ``config_data``, ``traffic_data``, ``limits``."""
    bench = benchmark()
    found = [w for w in bench["workloads"] if w["name"] == name]
    if not found:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; one of "
                       f"{[w['name'] for w in bench['workloads']]}")
    out = dict(found[0])
    conf = [c for c in bench["configs"] if c["name"] == out["config"]][0]
    out["config_data"] = _load(ROOT, conf["file"])
    out["traffic_data"] = _load(HERE, "traffic", out["traffic"] + ".json")
    out["limits"] = _load(HERE, "limits", name + ".json")
    out["end_to_end"] = [m for m in bench["end_to_end"]
                         if name in m.get("workloads", [name])]
    out["per_layer"] = [m for m in bench["per_layer"]
                        if name in m.get("workloads", [name])]
    return out


def base_seed(seed: int, sweep: int, seeds: dict) -> int:
    """``base_seed`` of sweep ``sweep`` of a run seeded ``seed``: a hash
    of the seed, then ``stride`` apart per sweep, modulo 2**31. The
    stride exceeds the span of seeds one scenario derives from its
    ``base_seed`` (``+1 + 1009 j`` per tenant, ``+2`` for congestion), so
    no two sweeps of a run share a random stream. Sweep -1 is the
    warm-up's."""
    digest = hashlib.sha256(f"{seeds['salt']}:{seed}".encode()).digest()
    start = int.from_bytes(digest[:8], "little")
    return (start + sweep * seeds["stride"]) % 2 ** 31


def sweep_axes(traffic: dict, seed: int) -> Dict[str, list]:
    """The grid axes of the sweep whose ``base_seed`` is ``seed``: the
    mix's ``axes``, then its ``seed_axis`` filled from ``seed``."""
    axes = {k: list(v) for k, v in traffic["axes"].items()}
    extra = traffic.get("seed_axis")
    if extra:
        axes[extra["path"]] = [(seed + k * extra["stride"]) % 2 ** 31
                               for k in range(extra["count"])]
    return axes


def set_path(tree, path: str, value) -> None:
    """Set a dotted path (integer segments index lists) in a dict tree."""
    keys = path.split(".")
    for k in keys[:-1]:
        tree = tree[int(k)] if k.isdigit() else tree[k]
    last = keys[-1]
    if last.isdigit():
        tree[int(last)] = value
    elif last not in tree:
        raise KeyError(f"axis path {path!r} names no field")
    else:
        tree[last] = value


def variants(scenario: dict, axes: Dict[str, list], seed: int
             ) -> List[Tuple[dict, dict]]:
    """Every grid point as ``(params, scenario dict)``, in the cartesian
    order of ``axes`` (the order a grid sweep returns its results in)."""
    out = []
    keys = list(axes)
    for combo in itertools.product(*(axes[k] for k in keys)):
        params = dict(zip(keys, combo))
        d = copy.deepcopy(scenario)
        d["base_seed"] = seed
        for path, value in params.items():
            set_path(d, path, value)
        out.append((params, d))
    return out


def tenant_iters(scenario: dict, n_variants: int) -> int:
    """Simulated tenant-iterations in one sweep: every tenant of every
    variant steps ``iters`` times (the reported ``warmup`` included)."""
    return n_variants * len(scenario["jobs"]) * scenario["iters"]
