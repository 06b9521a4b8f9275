"""Shared-fabric engine tests: single-job equivalence against the executable
spec (seed loop), multi-tenant contention and byte conservation, and
placement policies."""
import pytest

from repro.core import diagnose
from repro.fabric import (FabricEngine, JobSpec, SimConfig, fat_tree, place,
                          simulate, spanning_groups, tpu_pod)
from repro.fabric._reference import simulate_reference


# ---------------------------------------------------------------------------
# single-job equivalence: engine == seed loop, bit for bit
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n,coordination", [(4, False), (16, False),
                                            (16, True), (64, False),
                                            (64, True)])
def test_engine_matches_reference_step_times(n, coordination):
    cfg = SimConfig.fast(n, coordination=coordination, seed=3)
    new = simulate(cfg)
    ref = simulate_reference(cfg)
    assert new.step_times == ref.step_times          # exact, not approx
    assert new.link_bytes == ref.link_bytes


def test_engine_matches_reference_records():
    """Lazily materialized records equal the eagerly built seed records."""
    cfg = SimConfig.fast(8, coordination=True, seed=5)
    new, ref = simulate(cfg), simulate_reference(cfg)
    assert new.records == ref.records


@pytest.mark.slow
def test_engine_matches_reference_full_fidelity():
    """Full paper-horizon equivalence (the fast-preset tests above cover the
    same property on a shorter horizon)."""
    for coordination in (False, True):
        cfg = SimConfig.paper(64, coordination=coordination, seed=0)
        assert simulate(cfg).step_times == \
            simulate_reference(cfg).step_times


def test_simulate_records_feed_diagnostics():
    res = simulate(SimConfig.fast(16))
    rep = diagnose(res.per_rank_records())
    assert rep.n_ranks == 16
    assert rep.n_iters == res.cfg.iters


# ---------------------------------------------------------------------------
# multi-tenant: contention + conservation
# ---------------------------------------------------------------------------


def _fabric():
    return fat_tree(64, nodes_per_leaf=8)


def test_cotenant_on_shared_uplink_slows_job():
    """Job a spans leaves 0-1; a heavy co-tenant spanning leaves 1-2 loads
    up1 -> a's steps stretch even though a's own traffic never changed."""
    a = JobSpec("a", 12, nodes=tuple(range(0, 12)))
    b = JobSpec("b", 12, nodes=tuple(range(12, 24)), grad_bytes=4e9)
    solo = FabricEngine(_fabric(), [a], base_seed=0).run(150, warmup=20)
    duo = FabricEngine(_fabric(), [a, b], base_seed=0).run(150, warmup=20)
    assert duo.job("a").mean_step > solo.job("a").mean_step


def test_cotenant_on_disjoint_leaves_behind_fat_spine_is_benign():
    """Same co-tenant bytes, but no common up-link and a non-bottleneck
    spine: contention must NOT be charged (locality of interference)."""
    a = JobSpec("a", 16, nodes=tuple(range(0, 16)))
    b = JobSpec("b", 16, nodes=tuple(range(32, 48)), grad_bytes=2e9)
    solo = FabricEngine(_fabric(), [a], base_seed=0).run(150, warmup=20)
    duo = FabricEngine(_fabric(), [a, b], base_seed=0).run(150, warmup=20)
    assert duo.job("a").mean_step == pytest.approx(
        solo.job("a").mean_step, rel=1e-6)


def test_multijob_conserves_link_bytes():
    jobs = [JobSpec("a", 8, placement="scattered"),
            JobSpec("b", 8, placement="scattered", grad_bytes=2e9),
            JobSpec("c", 8, placement="compact", algo="tree")]
    res = FabricEngine(_fabric(), jobs, base_seed=1).run(120, warmup=10)
    per_job = {}
    for jr in res.jobs:
        for ln, b in jr.link_bytes.items():
            per_job[ln] = per_job.get(ln, 0.0) + b
    assert set(per_job) == set(res.link_bytes)
    for ln, total in res.link_bytes.items():
        assert per_job[ln] == pytest.approx(total, rel=1e-9)


def test_job_lookup_and_explicit_node_validation():
    res = FabricEngine(_fabric(), [JobSpec("a", 4)], base_seed=0).run(30, 5)
    assert res.job("a").name == "a"
    with pytest.raises(KeyError):
        res.job("ghost")
    with pytest.raises(ValueError):
        FabricEngine(_fabric(), [JobSpec("a", 4, nodes=(0, 1, 2, 3)),
                                 JobSpec("b", 2, nodes=(3, 4))],
                     base_seed=0)
    with pytest.raises(ValueError):
        FabricEngine(_fabric(), [JobSpec("a", 3, nodes=(1, 1, 2))],
                     base_seed=0)


def test_engine_run_is_one_shot():
    """Job clocks and congestion state carry over; a second run() must
    raise instead of silently mixing series."""
    eng = FabricEngine(_fabric(), [JobSpec("a", 4)], base_seed=0)
    eng.run(20, 5)
    with pytest.raises(RuntimeError):
        eng.run(20, 5)


# ---------------------------------------------------------------------------
# placement policies
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("policy", ["compact", "scattered", "striped",
                                    "random"])
@pytest.mark.parametrize("make_topo", [lambda: fat_tree(64, nodes_per_leaf=8),
                                       lambda: tpu_pod(4, ranks_per_pod=8)],
                         ids=["fat_tree", "tpu_pod"])
def test_placement_is_bijective(policy, make_topo):
    topo = make_topo()
    nodes = place(policy, topo, 10, seed=3)
    assert len(nodes) == 10 and len(set(nodes)) == 10
    assert all(0 <= nd < topo.n_ranks for nd in nodes)
    # co-tenant allocation respects already-taken nodes
    more = place(policy, topo, 6, taken=nodes, seed=4)
    assert len(more) == 6 and not set(nodes) & set(more)


def test_placement_capacity_error():
    topo = fat_tree(8)
    with pytest.raises(ValueError):
        place("compact", topo, 9)


def test_scattered_spans_more_groups_than_compact():
    topo = fat_tree(64, nodes_per_leaf=8)
    assert spanning_groups(topo, place("compact", topo, 8)) == 1
    assert spanning_groups(topo, place("scattered", topo, 8)) == 8


def test_scattered_placement_degrades_leaf_local_job():
    """A job that fits under one leaf pays the oversubscribed tier only when
    scattered -> the paper's locality-driven variance, reproduced."""
    topo = fat_tree(64, nodes_per_leaf=8)
    compact = FabricEngine(topo, [JobSpec("j", 8, placement="compact")],
                           base_seed=0).run(120, warmup=20)
    scattered = FabricEngine(topo, [JobSpec("j", 8, placement="scattered")],
                             base_seed=0).run(120, warmup=20)
    assert scattered.jobs[0].mean_step > 1.5 * compact.jobs[0].mean_step


# ---------------------------------------------------------------------------
# algo="auto": per-placement schedule selection
# ---------------------------------------------------------------------------


def test_auto_algo_resolved_per_placement():
    """JobSpec(algo="auto") resolves at placement time from the compiled
    schedules' byte exposure; the pick is visible on the result and is
    never slower (uncongested) than forcing any single algorithm."""
    topo = _fabric()
    res = FabricEngine(topo, [JobSpec("j", 16, placement="scattered",
                                      algo="auto")],
                       base_seed=0).run(60, warmup=10)
    picked = res.job("j").algo
    assert picked in ("ring", "tree", "hierarchical")
    nodes = tuple(res.job("j").nodes)
    forced = {}
    for algo in ("ring", "tree", "hierarchical"):
        r = FabricEngine(_fabric(), [JobSpec("j", 16, nodes=nodes,
                                             algo=algo)],
                         base_seed=0).run(60, warmup=10)
        forced[algo] = r.job("j").mean_step
    assert forced[picked] <= min(forced.values()) * 1.05


# ---------------------------------------------------------------------------
# fast preset keeps the paper's qualitative signatures in default tier-1
# ---------------------------------------------------------------------------


def test_fast_preset_keeps_scaling_signatures():
    runs = {n: simulate(SimConfig.fast(n)) for n in (4, 64)}
    assert runs[64].throughput / 64 < runs[4].throughput / 4
    assert runs[64].cv > runs[4].cv
