"""Spans and counters inside the batched sweep engine
(:mod:`repro.fabric.telemetry`), at test scale on the CPU.

Each test draws its scenarios from a base seed of its own, so the
engine's process-wide caches (which other tests in the worker share)
miss or hit exactly as the test expects."""
import numpy as np
import pytest

from repro.fabric import telemetry


@pytest.fixture(autouse=True)
def telemetry_off():
    """Every test starts and ends with telemetry off and nothing kept."""
    telemetry.disable()
    telemetry.take()
    yield
    telemetry.disable()
    telemetry.take()


def _grid(seed, *, fresh_seeds=False, iters=30):
    from repro.fabric.congestion import CongestionConfig
    from repro.fabric.engine import JobSpec
    from repro.fabric.scenario import Scenario, ScenarioGrid, TopologySpec

    base = Scenario(name="tm", topology=TopologySpec(n_nodes=16,
                                                     nodes_per_leaf=4),
                    jobs=[JobSpec("a", 8, placement="scattered")],
                    congestion=CongestionConfig(k_kick=0.25),
                    iters=iters, warmup=5, base_seed=seed)
    if fresh_seeds:
        axes = {"base_seed": [seed + 4096 * k for k in range(4)]}
    else:
        axes = {"congestion.u_mean": [0.1, 0.2, 0.3, 0.4]}
    return ScenarioGrid(base, axes)


def _children(records, parent):
    return [r for r in records if r.parent == parent]


def test_off_span_is_the_shared_noop_and_records_nothing():
    assert not telemetry.enabled()
    first = telemetry.span("fabric.sweep")
    assert first is telemetry.span("fabric.prep")
    with first:
        telemetry.count("fabric.engine_cache.hit")
    _grid(700_001).run(backend="jnp")
    snap = telemetry.take()
    assert snap.records == [] and snap.counters == {}


def test_on_spans_nest_by_layer_and_share_one_sweep_id():
    telemetry.enable()
    _grid(700_101).run(backend="jnp")
    snap = telemetry.take()
    recs = snap.records
    assert all(r.end_ns is not None and r.end_ns >= r.start_ns
               for r in recs)
    assert {r.sweep_id for r in recs} == {recs[0].sweep_id}
    (sweep,) = [i for i, r in enumerate(recs) if r.name == "fabric.sweep"]
    assert recs[sweep].parent is None

    preps = [i for i, r in enumerate(recs) if r.name == "fabric.prep"]
    assert len(preps) == 4
    for i in preps:
        assert recs[i].parent == sweep
        kids = _children(recs, i)
        assert [r.name for r in kids] == ["fabric.prep.engine",
                                          "fabric.prep.encode"]
        encode = recs.index(kids[1])
        assert sorted(r.name for r in _children(recs, encode)) == [
            "fabric.prep.compute_stream", "fabric.prep.gauss_stream"]

    runners = [i for i, r in enumerate(recs) if r.name == "fabric.runner"]
    assert len(runners) == 1                     # one structural group
    steps = [r.name for r in _children(recs, runners[0])]
    assert steps[0] in ("fabric.runner.launch", "fabric.runner.build")
    assert steps[1:] == ["fabric.runner.wait", "fabric.runner.fetch"]
    names = [r.name for r in _children(recs, sweep)]
    assert names.count("fabric.wrap") == 4
    assert names.count("fabric.stack") == 2      # grouping, one stack
    assert snap.counters.get("fabric.runner.build", 0) == \
        steps.count("fabric.runner.build")

    telemetry.enable()
    _grid(700_101).run(backend="jnp")           # same group: no build
    again = telemetry.take()
    assert "fabric.runner.build" not in again.counters
    assert not [r for r in again.records if r.name == "fabric.runner.build"]
    assert again.records[0].sweep_id > recs[0].sweep_id


def test_shared_seed_grid_hits_the_caches_after_its_first_variant():
    telemetry.enable()
    _grid(700_201).run(backend="jnp")
    c = telemetry.take().counters
    for cache in ("engine_cache", "compute_stream", "gauss_stream"):
        assert c[f"fabric.{cache}.miss"] == 1
        assert c[f"fabric.{cache}.hit"] == 3


def test_fresh_seed_grid_misses_every_cache_and_grows_them():
    telemetry.enable()
    before = telemetry.take().caches
    _grid(700_301, fresh_seeds=True).run(backend="jnp")
    snap = telemetry.take()
    for cache in ("engine_cache", "compute_stream", "gauss_stream"):
        assert snap.counters[f"fabric.{cache}.miss"] == 4
        assert f"fabric.{cache}.hit" not in snap.counters
        assert snap.caches[f"fabric.{cache}.entries"] == \
            before[f"fabric.{cache}.entries"] + 4
    # 30 iterations x 8 ranks of float64 per compute stream
    assert snap.caches["fabric.compute_stream.bytes"] >= \
        before["fabric.compute_stream.bytes"] + 4 * 30 * 8 * 8
    assert snap.caches["fabric.runners.entries"] >= 1


def test_compute_stream_serial_steps_are_counted_per_miss():
    """Entries plus exit checks of the spike chain, on a miss only: the
    Python model's own spike states give the count independently."""
    from repro.fabric.backend.jnp_engine import _compute_stream
    from repro.fabric.stragglers import ComputeModel, StragglerConfig

    heavy = StragglerConfig(spike_prob=0.3, spike_exit_prob=0.05,
                            heavy_frac=0.5)
    telemetry.enable()
    _compute_stream(heavy, 8, 700_601, 40)
    c = telemetry.take().counters
    cm = ComputeModel(heavy, 8, seed=700_601)
    before = np.zeros(8)
    exit_checks = entries = 0
    for _ in range(40):
        cm.sample()
        after = np.array(cm.spiking)
        exit_checks += int(np.count_nonzero(before))
        entries += int(np.count_nonzero((before == 0) & (after != 0)))
        before = after
    assert c["fabric.compute_stream.miss"] == 1
    assert c["fabric.compute_stream.serial_steps"] == \
        entries + exit_checks > 0

    _compute_stream(heavy, 8, 700_601, 40)
    c = telemetry.take().counters
    assert c["fabric.compute_stream.hit"] == 1
    assert "fabric.compute_stream.serial_steps" not in c

    _compute_stream(StragglerConfig(spike_prob=0.0), 8, 700_602, 40)
    c = telemetry.take().counters
    assert c["fabric.compute_stream.miss"] == 1
    assert c["fabric.compute_stream.serial_steps"] == 0


def test_series_are_bit_identical_with_telemetry_on_and_off():
    off = _grid(700_401, fresh_seeds=True).run(backend="jnp")
    telemetry.enable()
    on = _grid(700_401, fresh_seeds=True).run(backend="jnp")
    assert telemetry.take().records
    assert len(on) == len(off) == 4
    for (p_off, r_off), (p_on, r_on) in zip(off, on):
        assert p_off == p_on
        a = np.array(r_off.series("a"))
        b = np.array(r_on.series("a"))
        assert a.shape == b.shape == (25,)
        assert a.tobytes() == b.tobytes()


def test_record_cap_counts_what_it_drops(monkeypatch):
    monkeypatch.setattr(telemetry, "MAX_RECORDS", 3)
    telemetry.enable()
    with telemetry.span("outer"):
        for _ in range(4):
            with telemetry.span("inner"):
                pass
    snap = telemetry.take()
    assert [r.name for r in snap.records] == ["outer", "inner", "inner"]
    assert [r.parent for r in snap.records] == [None, 0, 0]
    assert snap.counters == {"fabric.telemetry.dropped": 2}
    telemetry.count("fabric.runner.rerun", 2)
    assert telemetry.take().counters == {"fabric.runner.rerun": 2}


def test_take_inside_a_span_leaves_it_open_and_unparents_later_spans():
    telemetry.enable()
    with telemetry.span("fabric.sweep"):
        early = telemetry.take()
        with telemetry.span("fabric.prep"):
            pass
    late = telemetry.take()
    assert [(r.name, r.end_ns) for r in early.records] == [
        ("fabric.sweep", None)]
    (prep,) = late.records
    assert prep.parent is None and prep.sweep_id == early.records[0].sweep_id
