"""Tests of the benchmark harness, at test scale on the CPU."""
import dataclasses
import json
import math
import os
import subprocess
import sys
import time
from types import SimpleNamespace

import numpy as np
import pytest

from bench import cells, harness, reference, trace as tr

ROOT = cells.ROOT
CELLS = sorted(w["name"] for w in cells.benchmark()["workloads"])


def shrink(scn, traffic):
    """A cell at test scale: 30 iterations (5 of them warm-up), every
    value of every axis, and 4 seeds on a seed axis. No axis is cut, so
    that two of its values that wrongly share a compiled runner show
    here."""
    scn = json.loads(json.dumps(scn))
    scn["iters"], scn["warmup"] = 30, 5
    traffic = dict(traffic)
    if "seed_axis" in traffic:
        traffic["seed_axis"] = dict(traffic["seed_axis"], count=4)
    return scn, traffic


def cotenant(fairness):
    """Four 8-rank striped co-tenants on a 64-node fat tree: the plain
    reference's contended path at test scale."""
    from repro.fabric.congestion import CongestionConfig
    from repro.fabric.engine import JobSpec
    from repro.fabric.scenario import Policies, Scenario, TopologySpec
    return Scenario(
        name="cotenant", topology=TopologySpec(n_nodes=64,
                                               nodes_per_leaf=8),
        jobs=[JobSpec(f"t{j}", 8, placement="striped",
                      grad_bytes=2e9 * (1 + j % 3), weight=1.0 + j,
                      priority=j % 3) for j in range(4)],
        policies=Policies(fairness=fairness),
        congestion=CongestionConfig(k_kick=0.25), iters=60, warmup=0,
        base_seed=4242).to_dict()


def run_small(name, traced=False, seed=2 ** 31 + 12345, trace_dir=None):
    import jax
    return harness.run(name, seed, 0.3, traced, jax.devices()[0], 0.0,
                       shrink=shrink, trace_dir=trace_dir)


# -- trace reduction --------------------------------------------------------


def test_trace_reduction_on_a_synthetic_trace():
    ops = [("fusion.1", 1.0, 2.0), ("custom-call.2", 1.5, 2.5),
           ("fusion.1", 4.0, 5.0)]
    intervals = [(s, e) for _, s, e in ops]
    window = (0.5, 6.0)
    assert tr.union(intervals) == [(1.0, 2.5), (4.0, 5.0)]
    assert tr.busy(intervals, window) == pytest.approx(2.5)
    assert tr.busy(intervals, (2.0, 4.5)) == pytest.approx(1.0)
    idle = tr.gaps(intervals, window)
    assert idle == [(0.5, 1.0), (2.5, 4.0), (5.0, 6.0)]
    assert sum(e - s for s, e in idle) == pytest.approx(5.5 - 2.5)
    spans = [("sweep_call", 0.0, 3.0), ("wrap", 2.0, 2.8),
             ("runner", 3.5, 5.5)]
    assert tr.innermost(spans) == [(0.0, 2.0, "sweep_call"),
                                   (2.0, 2.8, "wrap"),
                                   (2.8, 3.0, "sweep_call"),
                                   (3.5, 5.5, "runner")]
    got = tr.attribute(idle, spans)
    assert got == pytest.approx({"sweep_call": 0.7, "wrap": 0.3,
                                 "runner": 1.0, "host_other": 1.0})
    assert tr.op_seconds(ops) == pytest.approx({"fusion.1": 2.0,
                                                "custom-call.2": 1.0})
    assert tr.op_seconds(ops, (1.8, 4.5))["fusion.1"] == pytest.approx(0.7)
    assert tr.top({"a": 1.0, "b": 3.0, "c": 2.0}, 2) == [["b", 3.0],
                                                         ["c", 2.0]]
    t = tr.Trace(ops, [], [("sweep", 0.5, 3.0), ("sweep", 3.0, 6.0)])
    assert t.window() == (0.5, 6.0)


def _plane(name, **lines):
    ev = lambda n, s, d: SimpleNamespace(name=n, start_ns=s, duration_ns=d)
    return SimpleNamespace(name=name, lines=[
        SimpleNamespace(name=k, events=[ev(*e) for e in v])
        for k, v in lines.items()])


def test_trace_reads_device_lines_by_name():
    host = _plane("/host:CPU", python=[("bench.sweep", 0, 100),
                                       ("other", 10, 5)])
    tpu = _plane("/device:TPU:0", **{
        "XLA Ops": [("fusion", 20, 10)],
        "XLA Modules": [("jit_single(1)", 15, 30)],
        "Steps": [("0", 0, 100), ("1", 0, 100)]})
    t = tr.from_planes([host, tpu, _plane("/device:TPU:1")])
    ns = lambda evs: [(n, round(s * 1e9), round(e * 1e9)) for n, s, e in evs]
    assert ns(t.ops) == [("fusion", 20, 30)]
    assert ns(t.modules) == [("jit_single(1)", 15, 45)]
    assert ns(t.spans) == [("sweep", 0, 100)]
    assert tr.from_planes([host]).ops == []     # no TPU: nothing to read
    # no stand-in for a missing ops line, however busy another line is
    with pytest.raises(ValueError, match="XLA Ops"):
        tr.from_planes([host, _plane("/device:TPU:0", Steps=[("0", 0, 9)])])


# -- cells ------------------------------------------------------------------


def check_cell_files(spec):
    """What a cell's own files have to hold together, whatever the
    deployment: the configuration is a ``Scenario`` as it is written
    (``to_dict()`` gives the file back), the harness's variants are
    ``ScenarioGrid``'s (which raises on an axis path that resolves to no
    field) and its count of tenant-iterations is theirs."""
    from repro.fabric.scenario import Scenario, ScenarioGrid
    scn = spec["config_data"]["scenario"]
    base = Scenario.from_dict(scn)
    assert base.to_dict() == scn
    traffic = spec["traffic_data"]
    bs = cells.base_seed(1, 0, traffic["seeds"])
    sweep = cells.sweep_axes(traffic, bs)
    mine = cells.variants(scn, sweep, bs)
    want = ScenarioGrid(base.replace(base_seed=bs), sweep)
    assert len(mine) == len(want) == math.prod(len(v)
                                               for v in sweep.values())
    for (p, d), (q, s) in zip(mine, want):
        assert p == q
        assert dataclasses.replace(Scenario.from_dict(d), name=s.name) == s
    # an axis that changes the jobs or their length changes the count
    assert cells.tenant_iters(scn, len(mine)) == sum(
        len(d["jobs"]) * d["iters"] for _, d in mine)


@pytest.mark.parametrize("name", CELLS)
def test_cell_files_build_their_grids(name):
    check_cell_files(cells.cell(name))


# Table 1's 64-node runs as the repository fits them, by configuration
PAPER = {"table1_n64": {"coordination": False},
         "table1_n64_paced": {"coordination": True}}
TABLE1 = [n for n in CELLS if cells.cell(n)["config"] in PAPER]


@pytest.mark.parametrize("name", TABLE1)
def test_cell_files_build_the_repository_grids(name):
    """A cell on one of Table 1's configurations is that configuration's
    ``SimConfig.paper`` scenario; its ``calibrate_grid`` is the grid
    ``calibrate()`` sweeps around a fit by default, its ``seed_grid``
    the configuration as it stands over 64 seeds."""
    from repro.fabric import SimConfig, scenario_from
    from repro.fabric.scenario import Scenario
    spec = cells.cell(name)
    base = scenario_from(SimConfig.paper(64, seed=0,
                                         **PAPER[spec["config"]]))
    got = Scenario.from_dict(spec["config_data"]["scenario"])
    assert dataclasses.replace(got, name=base.name) == base
    traffic = spec["traffic_data"]
    if spec["traffic"] == "calibrate_grid":
        u = base.congestion.u_mean
        axes = {"congestion.u_mean": sorted({u * 0.5, u, u * 1.5}),
                "congestion.u_sigma": [0.04, 0.08, 0.16]}
        assert list(traffic["axes"]) == list(axes)
        for k, v in axes.items():
            assert traffic["axes"][k] == pytest.approx(v)
        assert "seed_axis" not in traffic
    elif spec["traffic"] == "seed_grid":
        assert traffic["axes"] == {}
        assert traffic["seed_axis"]["path"] == "base_seed"
        assert traffic["seed_axis"]["count"] == 64


def test_benchmark_json_names_every_file():
    bench = cells.benchmark()
    for m in bench["per_layer"]:
        assert os.path.exists(os.path.join(ROOT, "bench", "metrics",
                                           m["name"] + ".py"))
    for w in bench["workloads"]:
        cells.cell(w["name"])                      # loads all three files


def check_seeds(traffic, seed):
    """No two sweeps of a run, and no two variants of a sweep, share a
    random stream."""
    seeds = traffic["seeds"]
    got = [cells.base_seed(seed, i, seeds) for i in range(-1, 3000)]
    assert all(0 <= b < 2 ** 31 for b in got)
    assert got == [cells.base_seed(seed, i, seeds) for i in range(-1, 3000)]
    assert cells.base_seed(seed + 1, 0, seeds) != got[1]
    # every seed a variant uses, and those a scenario derives from it
    # (base + 2, base + 1 + 1009 j per tenant), belongs to one variant of
    # one sweep
    used = sorted(s for b in got
                  for s in cells.sweep_axes(traffic, b)["base_seed"])
    assert all(b - a >= traffic["seed_axis"]["stride"]
               for a, b in zip(used, used[1:]))
    assert 2 < traffic["seed_axis"]["stride"]
    assert traffic["seed_axis"]["stride"] * traffic["seed_axis"]["count"] \
        <= seeds["stride"]


SEEDED = [n for n in CELLS if "seed_axis" in cells.cell(n)["traffic_data"]]


@pytest.mark.parametrize("seed", [0, 7, 2 ** 31 + 5, 2 ** 33 - 1])
@pytest.mark.parametrize("name", SEEDED)
def test_sweep_seeds_never_share_a_stream(name, seed):
    check_seeds(cells.cell(name)["traffic_data"], seed)


def test_run_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "table1_n64.calibrate_grid", "--seed", "1",
                        "--seconds", "1", "--trace", "0"],
                       cwd=ROOT, env=env, capture_output=True, text=True,
                       timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


# -- the plain reference ----------------------------------------------------


FAIRNESS = ["maxmin", "wfq", "strict_priority", "single"]


@pytest.mark.parametrize(
    "fairness,seed",
    [pytest.param(f, 4242, id=f) for f in FAIRNESS]
    + [pytest.param(f, 2 ** 31 - 7, id=f"{f}-high_seed") for f in FAIRNESS])
def test_plain_reference_is_the_engine_bit_for_bit(fairness, seed):
    from repro.fabric.scenario import Scenario
    if fairness == "single":                    # the cells' own job
        scn = cells.cell("table1_n64.calibrate_grid")["config_data"]
        scn = json.loads(json.dumps(scn["scenario"]))
        scn["iters"], scn["warmup"] = 60, 5
    else:
        scn = cotenant(fairness)
    scn["base_seed"] = seed
    res = Scenario.from_dict(scn).run(backend="reference")
    want = np.stack([res.series(j["name"]) for j in scn["jobs"]], axis=1)
    got = reference.series(scn)
    assert got.shape == want.shape
    assert np.array_equal(got, want)


def _paced(case, seed):
    """The paced cell's job at test scale: alone, or cut to 32 ranks
    beside an unpaced copy of itself on the other half of the fabric,
    the two sharing the spine."""
    spec = cells.cell("table1_n64_paced.calibrate_grid")
    scn, _ = shrink(spec["config_data"]["scenario"], spec["traffic_data"])
    if case == "beside_unpaced":
        paced = dict(scn["jobs"][0], n_ranks=32)
        scn["jobs"] = [paced, dict(paced, name="unpaced", pacing=None)]
    scn["base_seed"] = seed
    return scn


@pytest.mark.parametrize("seed", [4242, 2 ** 31 - 7])
@pytest.mark.parametrize("case", ["single", "beside_unpaced"])
def test_paced_reference_is_the_engine_bit_for_bit(case, seed):
    from repro.fabric.scenario import Scenario
    scn = _paced(case, seed)
    res = Scenario.from_dict(scn).run(backend="reference")
    want = np.stack([res.series(j["name"]) for j in scn["jobs"]], axis=1)
    got = reference.series(scn)
    assert got.shape == want.shape
    assert np.array_equal(got, want)
    paced = [j for j, s in enumerate(scn["jobs"]) if s["pacing"]]
    for s in scn["jobs"]:
        s["pacing"] = None
    unpaced = reference.series(scn)
    assert not np.array_equal(got[:, paced], unpaced[:, paced])


def test_plain_reference_refuses_what_it_does_not_model():
    scn = cells.cell("table1_n64.calibrate_grid")["config_data"]["scenario"]
    scn = json.loads(json.dumps(scn))
    scn["jobs"][0]["algo"] = "tree"
    with pytest.raises(ValueError):
        list(reference.steps(scn))


# -- a whole run at test scale ----------------------------------------------


def rehearse(name, trace_dir):
    """Run cell ``name`` at test scale, plain and traced, and check what
    each run reports."""
    out = run_small(name)
    assert out["correct"] is True
    assert out["failed"] == 0 and out["attempted"] > 0
    assert set(out["metrics"]) == {m["name"] for m in
                                   cells.cell(name)["end_to_end"]}
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert list(out)[-1] == "checks"
    assert out["window"]["compiles"] == 0
    assert out["device"]["platform"] == "cpu"
    traced = run_small(name, traced=True, trace_dir=str(trace_dir))
    assert traced["correct"] is True
    assert {m["name"] for m in cells.cell(name)["per_layer"]
            if m["source"] == "program_span"} <= set(traced["metrics"])
    assert traced["device"]["window_s"] > 0
    assert {"device_ops", "idle_gaps"} <= set(traced["breakdown"])
    return traced


@pytest.mark.parametrize("name", CELLS)
def test_cpu_rehearsal_of_a_run(name, tmp_path):
    rehearse(name, tmp_path)


@pytest.mark.parametrize("donor", ["table1_n64_paced.calibrate_grid",
                                   "table1_n64.seed_grid"])
def test_a_cell_joins_by_its_files_alone(donor, tmp_path, monkeypatch):
    """A copy of ``donor`` under new names, added as new files and new
    ``BENCHMARK.json`` entries and nothing else, is checked and run by
    what its files state."""
    spec = cells.cell(donor)
    bench = cells.benchmark()
    conf = next(c for c in bench["configs"] if c["name"] == spec["config"])
    entry = next(w for w in bench["workloads"] if w["name"] == donor)
    name = "joined.joined_mix"
    files = {"configs/joined.json": spec["config_data"],
             "traffic/joined_mix.json": spec["traffic_data"],
             f"limits/{name}.json": spec["limits"]}
    for path, data in files.items():
        (tmp_path / "bench" / path).parent.mkdir(parents=True, exist_ok=True)
        (tmp_path / "bench" / path).write_text(json.dumps(data))
    bench["configs"].append(dict(conf, name="joined",
                                 file="bench/configs/joined.json"))
    bench["workloads"].append(dict(entry, name=name,
                                   config="joined", traffic="joined_mix"))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    monkeypatch.setattr(cells, "ROOT", str(tmp_path))
    monkeypatch.setattr(cells, "HERE", str(tmp_path / "bench"))

    joined = cells.cell(name)
    assert joined["config_data"] == spec["config_data"]
    # though no entry already in BENCHMARK.json names it, the joined cell
    # reports each of its donor's per-layer metrics, and each end-to-end
    # metric that lists no cells
    assert [m["name"] for m in joined["end_to_end"]] == [
        m["name"] for m in spec["end_to_end"] if "workloads" not in m]
    assert [m["name"] for m in joined["per_layer"]] == [
        m["name"] for m in spec["per_layer"]]
    check_cell_files(joined)
    if "seed_axis" in joined["traffic_data"]:
        check_seeds(joined["traffic_data"], 2 ** 31 + 5)
    traced = rehearse(name, tmp_path / "trace")
    spans = {m["name"] for m in spec["per_layer"]
             if m["source"] == "program_span"}
    assert spans and spans <= set(traced["metrics"])


def test_window_leaves_out_the_harness_bookkeeping(monkeypatch):
    count = harness._count_failed

    def slow(*args):
        time.sleep(0.2)
        return count(*args)

    monkeypatch.setattr(harness, "_count_failed", slow)
    t0 = time.perf_counter()
    out = run_small("table1_n64.calibrate_grid")
    w = out["window"]
    assert w["seconds"] >= 0.3
    assert w["seconds"] <= w["sweeps"] * w["sweep_s_max"] + 1e-9
    assert time.perf_counter() - t0 > w["seconds"] + 0.2 * w["sweeps"]
