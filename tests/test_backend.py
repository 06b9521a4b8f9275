"""Kernel-registry backend equivalence suite.

Every kernel in :data:`repro.fabric.backend.EQUIVALENCE_TIERS` is
asserted here at its *declared* tier — the tier table is the contract,
and this file is its enforcement:

  exact : bit-identical to the reference Python under float64
          (progressive-filling allocators, offered-bytes share — same
          operation sequence, stable sort, left-to-right sums)
  ulp   : within `tol` ULPs under float64 (pacing decide, busy-segment
          overlap — summation order legitimately differs)
  rtol  : whole-scenario series within relative `tol` under float64;
          the float32 production dtype is asserted at a looser bound
          (XLA fuses multiply-adds, and the simulation feeds rounding
          differences back through the AR(1) congestion state)

plus the registry mechanics (parse/dispatch/duplicate rejection,
nearest-backend error hints), the Pallas tier (the fused waterfill and
segment-overlap kernels of
:mod:`repro.fabric.backend.pallas_kernels`, asserted at the same
declared tiers — on CPU they run in interpret mode, so this file
exercises the identical kernel code CI ships to TPU), and the
``Scenario``/``ScenarioGrid``/``Policies.backend`` selection surfaces.
Runs in tier-1; the heavier grid sweeps carry the slow marker (CI's
pallas-interpret job runs the ``-k pallas`` subset under
``JAX_PLATFORMS=cpu``).
"""
import dataclasses
import random

import numpy as np
import pytest

from repro.fabric.backend import (BACKENDS, EQUIVALENCE_TIERS,
                                  JNP_SCENARIO_FAIRNESS, KERNELS,
                                  PALLAS_KERNELS, BackendError, KernelType,
                                  available_backends, get_kernel,
                                  register_kernel)

try:
    import jax
    HAVE_JAX = True
except ImportError:                   # registry tests still run
    HAVE_JAX = False

needs_jax = pytest.mark.skipif(not HAVE_JAX, reason="jax not installed")


def _within_ulps(got, want, n_ulps):
    """True when ``got`` is within ``n_ulps`` float64 ULPs of ``want``
    elementwise (``np.spacing`` is the ULP at each magnitude)."""
    a = np.asarray(got, dtype=np.float64)
    b = np.asarray(want, dtype=np.float64)
    bound = n_ulps * np.spacing(np.maximum(np.abs(a), np.abs(b)))
    return bool(np.all(np.abs(a - b) <= bound))


# ---------------------------------------------------------------------------
# registry mechanics
# ---------------------------------------------------------------------------


def test_catalogue_and_tier_table_agree():
    assert set(EQUIVALENCE_TIERS) == set(KERNELS)
    assert BACKENDS == ("reference", "jnp", "pallas")
    for tier, tol in EQUIVALENCE_TIERS.values():
        assert tier in ("exact", "ulp", "rtol")
        assert tol >= 0.0
        assert (tol == 0.0) == (tier == "exact")


def test_kernel_type_parse():
    assert KernelType.parse("jnp") is KernelType.JNP
    assert KernelType.parse("JNP") is KernelType.JNP
    assert KernelType.parse(None) is KernelType.REFERENCE
    assert KernelType.parse(None, KernelType.JNP) is KernelType.JNP
    assert KernelType.parse(KernelType.PALLAS) is KernelType.PALLAS
    with pytest.raises(BackendError, match="unknown backend"):
        KernelType.parse("cuda")


def test_unknown_kernel_and_reserved_backend_raise():
    with pytest.raises(BackendError, match="unknown kernel"):
        get_kernel("fft", KernelType.REFERENCE)
    # drr has no pallas registration (the quantized drain does not
    # vectorize) — a clean BackendError naming the nearest stand-in,
    # not a KeyError
    with pytest.raises(BackendError) as exc:
        get_kernel("drr_shares", KernelType.PALLAS)
    msg = str(exc.value)
    assert "no 'pallas' implementation" in msg
    assert "drr_shares" in msg
    assert "nearest supported backend: 'jnp'" in msg


def test_duplicate_registration_rejected():
    get_kernel("maxmin_shares", KernelType.REFERENCE)  # force the load
    with pytest.raises(ValueError, match="already registered"):
        register_kernel("maxmin_shares", KernelType.REFERENCE,
                        lambda *a: None)
    with pytest.raises(ValueError, match="unknown kernel"):
        register_kernel("fft", KernelType.REFERENCE, lambda *a: None)


@needs_jax
def test_every_kernel_has_its_declared_implementations():
    for name in KERNELS:
        want = {"reference", "jnp"}
        if name in PALLAS_KERNELS:
            want.add("pallas")
        assert set(available_backends(name)) == want, name


# ---------------------------------------------------------------------------
# exact tier: allocators + offered share, bit-identical under float64
# ---------------------------------------------------------------------------


def _rand_demands(rng, n):
    # zeros included on purpose: they exercise the stable-sort prefix
    return [0.0 if rng.random() < 0.2 else rng.uniform(0.0, 2.0)
            for _ in range(n)]


@needs_jax
@pytest.mark.parametrize("name", ["maxmin_shares", "wfq_shares",
                                  "strict_priority_shares", "drr_shares"])
def test_allocator_kernels_bit_exact_under_x64(name):
    tier, tol = EQUIVALENCE_TIERS[name]
    assert (tier, tol) == ("exact", 0.0)
    ref = get_kernel(name, KernelType.REFERENCE)
    fast = get_kernel(name, "jnp")
    rng = random.Random(5)
    with jax.enable_x64(True):
        for trial in range(60):
            n = rng.randint(1, 8)
            d = _rand_demands(rng, n)
            cap = rng.choice([0.5, 1.0, 2.0])
            if name == "strict_priority_shares":
                prios = np.array([float(rng.randint(0, 3))
                                  for _ in range(n)])
                want = ref(d, list(prios), cap)
                got = fast(np.array(d), prios, cap)
            elif name in ("wfq_shares", "drr_shares"):
                w = [rng.uniform(0.1, 2.0) for _ in range(n)]
                want = ref(d, w, cap)
                got = fast(np.array(d), np.array(w), cap)
            else:
                want = ref(d, cap)
                got = fast(np.array(d), cap)
            got = np.asarray(got)
            assert got.dtype == np.float64
            assert list(got) == want, (name, trial, d, cap)


@needs_jax
def test_offered_share_kernel_bit_exact_under_x64():
    ref = get_kernel("offered_share", KernelType.REFERENCE)
    fast = get_kernel("offered_share", "jnp")
    rng = random.Random(6)
    with jax.enable_x64(True):
        for trial in range(60):
            d_i = rng.uniform(0.05, 2.0)
            # own_bytes == 0.0 hits the RESIDUAL_SHARE floor on both paths
            own = 0.0 if rng.random() < 0.2 else rng.uniform(0.0, 5.0)
            k = rng.randint(1, 6)
            flows = [(rng.uniform(0.0, 3.0), rng.uniform(0.0, 5.0))
                     for _ in range(k)]
            want = ref(own, d_i, flows)
            got = float(fast(own, d_i,
                             np.array([f[0] for f in flows]),
                             np.array([f[1] for f in flows])))
            assert got == want, (trial, own, d_i, flows)


@needs_jax
def test_maxmin_kernel_zero_padding_is_exact():
    """vmap batching pads ragged co-tenant lists with zero demands; for
    the max-min allocator the padded result is *bit-identical* on the
    real entries (zeros stable-sort first, consume nothing, and the
    positional ``remaining / (n - pos)`` arithmetic is unchanged) — the
    property the jnp engine's fixed-width owner matrices rely on."""
    fast = get_kernel("maxmin_shares", "jnp")
    rng = random.Random(13)
    with jax.enable_x64(True):
        for _ in range(30):
            n = rng.randint(1, 6)
            d = [rng.uniform(0.0, 2.0) for _ in range(n)]
            base = np.asarray(fast(np.array(d), 1.0))
            for pad in (1, 3):
                padded = np.asarray(fast(np.array(d + [0.0] * pad), 1.0))
                assert list(padded[:n]) == list(base)
                assert list(padded[n:]) == [0.0] * pad


@needs_jax
@pytest.mark.parametrize("name", ["maxmin_shares", "wfq_shares"])
def test_allocator_kernels_vmap_batch_matches_per_row(name):
    """One batched call is the whole point of the backend — it must give
    the same bits as calling the kernel row by row."""
    fast = get_kernel(name, "jnp")
    rng = np.random.default_rng(3)
    D = rng.uniform(0.0, 2.0, size=(16, 5))
    with jax.enable_x64(True):
        if name == "wfq_shares":
            W = rng.uniform(0.1, 2.0, size=(16, 5))
            batched = np.asarray(jax.vmap(
                lambda d, w: fast(d, w, 1.0))(D, W))
            rows = np.stack([np.asarray(fast(D[i], W[i], 1.0))
                             for i in range(16)])
        else:
            batched = np.asarray(jax.vmap(lambda d: fast(d, 1.0))(D))
            rows = np.stack([np.asarray(fast(D[i], 1.0))
                             for i in range(16)])
    assert (batched == rows).all()


# ---------------------------------------------------------------------------
# ulp tier: segment overlap + pacing decide
# ---------------------------------------------------------------------------


@needs_jax
def test_segment_overlap_kernel_within_ulp_tier():
    tier, tol = EQUIVALENCE_TIERS["segment_overlap"]
    assert tier == "ulp"
    fast = get_kernel("segment_overlap", "jnp")
    rng = random.Random(7)
    with jax.enable_x64(True):
        for trial in range(60):
            k = rng.randint(1, 12)
            starts = np.array([rng.uniform(0.0, 10.0) for _ in range(k)])
            ends = np.array([s + rng.uniform(-1.0, 4.0) for s in starts])
            for j in range(k):                # empty ring slots: end=-inf
                if rng.random() < 0.25:
                    ends[j] = -np.inf
            s_i = rng.uniform(0.0, 10.0)
            e_i = s_i + rng.uniform(0.0, 5.0)
            # the reference arithmetic inside engine.link_overlaps:
            # clamp-and-skip guard, left-to-right accumulation
            want = 0.0
            for s_k, e_k in zip(starts, ends):
                ov = min(e_i, e_k) - max(s_i, s_k)
                if ov > 0.0:
                    want += ov
            got = float(fast(s_i, e_i, starts, ends))
            assert _within_ulps(got, want, tol), (trial, got, want)


@needs_jax
@pytest.mark.parametrize("window", [6, 32])
def test_pacing_decide_kernel_within_ulp_tier(window):
    """The jnp kernel consumes the same ``(n, window)`` ring-buffer
    state a live :class:`PacingBank` holds; with the cursor at 0 (whole
    window wraps) the two must agree within the declared ULP budget on
    both the bounded delays and the carried internal delay state. Window
    6 is the fitted Table 1 controller's, 32 ``PacingConfig``'s default."""
    from repro.configs.base import PacingConfig
    from repro.core.pacing import PacingBank

    tier, tol = EQUIVALENCE_TIERS["pacing_decide"]
    assert tier == "ulp"
    fast = get_kernel("pacing_decide", "jnp")
    cfg = PacingConfig(enabled=True, window=window, cv_threshold=0.05,
                       skew_threshold=0.04, max_delay_frac=0.5, gain=0.8,
                       decay=0.8, warmup_iters=4)
    n = 8
    bank = PacingBank(cfg, n)
    rng = random.Random(9)
    with jax.enable_x64(True):
        for _ in range(5):
            for _ in range(cfg.window):   # full wraps keep the cursor at 0
                bank.observe(
                    np.array([abs(rng.gauss(0.02, 0.03))
                              for _ in range(n)]),
                    np.array([0.2 + rng.gauss(0.0, 0.02)
                              for _ in range(n)]))
            assert bank._pos == 0
            waits, steps = bank._bw.copy(), bank._bs.copy()
            early, delay = bank._be.copy(), bank._delay.copy()
            seen = bank._seen
            want = bank.decide()          # mutates bank._delay
            got, new_delay = fast(waits, steps, early, delay, seen, cfg)
            assert _within_ulps(np.asarray(got), want, tol)
            assert _within_ulps(np.asarray(new_delay), bank._delay, tol)


def _sorted_row_medians(bufs, valid, count):
    """The window medians as ``bank_decide`` took them before the min/max
    network: a device sort of each masked row, then ranks ``count // 2``
    and ``count // 2 - 1``."""
    import jax.numpy as jnp

    def rowmedian(buf):
        n = buf.shape[0]
        srt = jnp.sort(jnp.where(valid, buf, jnp.inf), axis=1)
        hi = jnp.take_along_axis(
            srt, jnp.full((n, 1), count // 2), axis=1)[:, 0]
        lo = jnp.take_along_axis(
            srt, jnp.full((n, 1), jnp.maximum(count // 2 - 1, 0)),
            axis=1)[:, 0]
        return jnp.where(count % 2 == 1, hi, 0.5 * (lo + hi))

    return jnp.stack([rowmedian(b) for b in bufs])


def _bits(a):
    a = np.asarray(a)
    return a.dtype, a.shape, a.tobytes()


@needs_jax
@pytest.mark.parametrize("x64", [False, True], ids=["float32", "float64"])
@pytest.mark.parametrize("w", [2, 3, 5, 6, 7, 8, 32])
def test_bank_decide_network_medians_are_the_sorted_rows_bit_for_bit(
        w, x64, monkeypatch):
    """``bank_decide`` with the min/max network against the same function
    with the sort-based medians, on traced cursors and counts as the
    engine's scan passes them: every fill ``count`` from 1 to ``w``,
    several cursors once full, rows with ties, and garbage in the slots
    a partial window leaves out (they rank as ``+inf``). Both returned
    arrays and the medians themselves must match bit for bit."""
    from repro.fabric.backend import jnp_kernels as K

    kw = dict(enabled=True, warmup_iters=2.0, cv_threshold=0.05,
              skew_threshold=0.04, gain=0.85, decay=0.8,
              max_delay_frac=0.1)      # small: the bound reads med_step

    def sorted_decide(*args):
        with monkeypatch.context() as m:      # in force while tracing
            m.setattr(K, "_window_medians", _sorted_row_medians)
            return K.bank_decide(*args, **kw)

    network = jax.jit(lambda *a: K.bank_decide(*a, **kw))
    sorted_ = jax.jit(sorted_decide)
    net_med = jax.jit(K._window_medians)
    srt_med = jax.jit(_sorted_row_medians)
    rng = np.random.default_rng(1000 + w)
    n = 16
    ties = np.array([0.0, 0.01, 0.02, 0.02, 0.05, 0.2])
    with jax.enable_x64(x64):
        dt = np.float64 if x64 else np.float32
        for count in range(1, w + 1):
            cursors = {count % w} if count < w else {0, 1, w // 2, w - 1}
            for pos in sorted(cursors):
                waits = np.where(rng.random((n, w)) < 0.5,
                                 rng.choice(ties, (n, w)),
                                 rng.uniform(0.0, 0.06, (n, w)))
                steps = 0.2 + rng.choice(ties, (n, w))
                steps[: n // 2] = 0.2                  # whole rows tied
                early = waits + rng.uniform(0.0, 0.02, (n, w))
                for buf in (waits, steps, early):      # outside the window
                    buf[:, count:] = rng.uniform(-1.0, 10.0, (n, w - count))
                delay = rng.uniform(0.0, 0.05, n)
                args = [x.astype(dt) for x in (waits, steps, early, delay)]
                args += [np.int32(pos), np.int32(count), np.int32(count + 2)]
                got, want = network(*args), sorted_(*args)
                for g, e in zip(got, want):
                    assert _bits(g) == _bits(e), (count, pos)
                bufs = np.stack(args[:2])
                valid = np.arange(w) < count
                assert _bits(net_med(bufs, valid, np.int32(count))) == \
                    _bits(srt_med(bufs, valid, np.int32(count))), (count, pos)


@needs_jax
def test_paced_runner_has_no_sort_and_counts_the_network(monkeypatch):
    """A paced job's compiled runner takes the window medians without a
    device sort, and building it counts ``fabric.runner.median_network``
    once for the job."""
    import re

    from repro.fabric import telemetry
    from repro.fabric.backend import jnp_engine

    scn = _scenario(paced=True, iters=24)
    scn = dataclasses.replace(scn, jobs=scn.jobs[:1])
    prep = jnp_engine._prep(scn)
    data = {k: np.stack([v]) for k, v in prep.data.items()}
    monkeypatch.setattr(jnp_engine, "_RUNNERS", {})
    telemetry.disable()
    telemetry.take()
    telemetry.enable()
    try:
        fn = jnp_engine._get_runner(prep.sig, prep.static, KernelType.JNP,
                                    scn.iters)
        counters = telemetry.take().counters
    finally:
        telemetry.disable()
        telemetry.take()
    assert counters["fabric.runner.median_network"] == 1
    hlo = fn.lower(data).as_text(dialect="hlo")
    assert re.search(r"\bwhile\(", hlo)                # the lowered scan
    assert not re.search(r"\bsort\(", hlo)


# ---------------------------------------------------------------------------
# rtol tier: whole scenarios, plus the selection surfaces
# ---------------------------------------------------------------------------


def _scenario(fairness="maxmin", *, backend=None, paced=False, name="bk",
              tenants=2, placement="compact", iters=40, warmup=5):
    from repro.fabric.congestion import CongestionConfig
    from repro.fabric.engine import JobSpec
    from repro.fabric.scenario import Policies, Scenario, TopologySpec

    pol = {} if backend is None else {"backend": backend}
    if tenants != 2:
        # co-tenants of three sizes, weights and priority classes
        jobs = [JobSpec(f"t{j}", 8, placement=placement,
                        grad_bytes=2e9 * (1 + j % 3), weight=1.0 + j,
                        priority=j % 3) for j in range(tenants)]
    elif fairness == "strict_priority":
        jobs = [JobSpec("a", 16, priority=5), JobSpec("b", 16, priority=0)]
    else:
        jobs = [JobSpec("a", 16), JobSpec("b", 16)]
    if paced:
        from repro.configs.base import PacingConfig
        import dataclasses
        pc = PacingConfig(enabled=True, window=6, cv_threshold=0.05,
                          skew_threshold=0.04, max_delay_frac=0.5,
                          gain=0.8, decay=0.8, warmup_iters=4)
        jobs = [dataclasses.replace(j, pacing=pc) for j in jobs]
    return Scenario(
        name=name,
        topology=TopologySpec(n_nodes=32, nodes_per_leaf=8),
        jobs=jobs,
        congestion=CongestionConfig(k_kick=0.25),
        policies=Policies(fairness=fairness, **pol),
        iters=iters, warmup=warmup)


def _series_close(ref_res, jnp_res, rtol):
    for job in ref_res.scenario.jobs:
        a = np.array(ref_res.series(job.name))
        b = np.array(jnp_res.series(job.name))
        assert a.shape == b.shape and len(a) > 0
        assert np.allclose(a, b, rtol=rtol, atol=0.0), \
            (job.name, float(np.max(np.abs(a - b) / np.abs(a))))


# Four tenants sharing every leaf up-link (striped/scattered) make the
# step dynamics sensitive to rounding. Under wfq the reference run
# against itself with u_mean moved by one ulp leaves the rtol tier at
# iteration 26 (scattered) or 62 (striped), and the runner's own rounding
# does the same from iteration 24. The per-iteration tier is asserted
# over that horizon; the two tests after this one cover long runs.
_TIER_CASES = [pytest.param(f, 2, "compact", 40, id=f)
               for f in JNP_SCENARIO_FAIRNESS] + [
    pytest.param(f, 4, pl, 20, id=f"{f}-4-{pl}")
    for pl in ("striped", "scattered") for f in JNP_SCENARIO_FAIRNESS]


@needs_jax
@pytest.mark.parametrize("fairness,tenants,placement,iters", _TIER_CASES)
def test_scenario_kernel_rtol_tier_under_x64(fairness, tenants, placement,
                                             iters):
    tier, tol = EQUIVALENCE_TIERS["scenario"]
    assert tier == "rtol"
    scn = _scenario(fairness, tenants=tenants, placement=placement,
                    iters=iters, warmup=4 if tenants != 2 else 5)
    ref = scn.run()                       # reference backend (default)
    with jax.enable_x64(True):
        fast = scn.run(backend="jnp")
    _series_close(ref, fast, tol)


@needs_jax
def test_cotenant_means_hold_past_the_rounding_horizon():
    """Past the horizon the four-tenant series are different but equally
    valid trajectories; what must still agree is each tenant's mean step
    (busy segments lost from a too-short ring moved one by 6%)."""
    scn = _scenario("wfq", tenants=4, placement="striped", iters=200,
                    warmup=20)
    ref = scn.run()
    with jax.enable_x64(True):
        fast = scn.run(backend="jnp")
    for job in scn.jobs:
        a = np.mean(ref.series(job.name))
        b = np.mean(fast.series(job.name))
        assert abs(b / a - 1.0) < 1e-2, (job.name, a, b)


@needs_jax
def test_segment_ring_grows_until_no_live_segment_is_lost():
    """A fast tenant next to one three times slower reads the slow one's
    busy segments from far more than ``SEG_CAPACITY`` iterations back;
    the runner must lengthen its ring rather than overwrite them."""
    from repro.fabric.backend import jnp_engine
    from repro.fabric.engine import JobSpec

    scn = _scenario("maxmin", iters=200)
    scn = dataclasses.replace(scn, jobs=[
        JobSpec("a", 16, placement="striped", grad_bytes=1e9),
        JobSpec("b", 16, placement="striped", grad_bytes=8e9)])
    assert scn.iters > 2 * jnp_engine.SEG_CAPACITY
    ref = scn.run()
    with jax.enable_x64(True):
        fast = scn.run(backend="jnp")
    _series_close(ref, fast, EQUIVALENCE_TIERS["scenario"][1])


@needs_jax
def test_scenario_kernel_float32_production_tolerance():
    """The float32 default is the production fast path; per-iteration
    rounding feeds back through the AR(1) congestion state, so the bound
    is necessarily looser than the float64 tier."""
    scn = _scenario("maxmin")
    ref = scn.run()
    fast = scn.run(backend="jnp")
    _series_close(ref, fast, 5e-2)
    for jname in ("a", "b"):
        a = np.array(ref.series(jname))
        b = np.array(fast.series(jname))
        assert abs(float(b.mean()) / float(a.mean()) - 1.0) < 1e-2


@needs_jax
def test_paced_scenario_equivalence_under_x64():
    scn = _scenario("maxmin", paced=True)
    ref = scn.run()
    with jax.enable_x64(True):
        fast = scn.run(backend="jnp")
    _series_close(ref, fast, EQUIVALENCE_TIERS["scenario"][1])


@needs_jax
def test_policies_backend_field_is_the_declarative_default():
    """``Policies.backend`` selects jnp without a ``run()`` argument, the
    field survives the JSON round trip, and an explicit ``run(backend=)``
    argument overrides the field in both directions."""
    from repro.fabric.scenario import Scenario

    scn = _scenario("maxmin", backend="jnp")
    assert Scenario.from_json(scn.to_json()).policies.backend == "jnp"
    via_field = scn.run()
    via_arg = _scenario("maxmin").run(backend="jnp")
    for jname in ("a", "b"):
        assert via_field.series(jname) == via_arg.series(jname)
    # override: the jnp-default scenario forced back onto the reference
    # path is bit-identical to a plain reference run
    ref = scn.run(backend="reference")
    want = _scenario("maxmin").run()
    for jname in ("a", "b"):
        assert ref.series(jname) == want.series(jname)


@needs_jax
def test_grid_batched_run_matches_per_variant_reference():
    """`ScenarioGrid.run(backend="jnp")` batches every variant through
    one vmapped program; results must come back in grid order and match
    each variant's sequential reference run."""
    from repro.fabric.scenario import ScenarioGrid

    grid = ScenarioGrid(_scenario("maxmin"), {
        "congestion.u_mean": [0.2, 0.35],
        "congestion.k_burst": [0.5, 1.5],
    })
    results = grid.run(backend="jnp")
    variants = grid.scenarios()
    assert len(results) == len(variants) == 4
    for (params, res), scn in zip(results, variants):
        _series_close(scn.run(), res, 5e-2)


# ---------------------------------------------------------------------------
# unsupported-feature error paths
# ---------------------------------------------------------------------------


def test_policies_rejects_unknown_backend():
    from repro.fabric.scenario import Policies, ScenarioError
    with pytest.raises(ScenarioError, match="unknown backend"):
        Policies(backend="cuda").validate()


def test_scenario_rejects_jnp_with_unsupported_fairness():
    from repro.fabric.scenario import ScenarioError
    with pytest.raises(ScenarioError, match="fairness"):
        _scenario("offered", backend="jnp").validate()


def test_scenario_pallas_rejects_unsupported_fairness_with_hint():
    """The batched runner's BackendError names the offending feature and
    the nearest backend that supports it — for the eager `validate()`
    path and for a direct `run()` alike."""
    from repro.fabric.scenario import ScenarioError
    with pytest.raises(ScenarioError, match="fairness"):
        _scenario("drr", backend="pallas").validate()
    with pytest.raises(BackendError) as exc:
        _scenario("offered").run(backend="pallas")
    msg = str(exc.value)
    assert "backend='pallas'" in msg
    assert "fairness='offered'" in msg
    assert "nearest supported backend: 'reference'" in msg


def test_scenario_pallas_rejects_event_timelines_with_hint():
    import dataclasses

    from repro.fabric import Arrival
    from repro.fabric.scenario import ScenarioError

    base = _scenario("maxmin")
    timed = dataclasses.replace(
        base, jobs=None, events=(Arrival(0.0, base.jobs[0]),),
        horizon=5.0)
    with pytest.raises(ScenarioError, match="static-jobs"):
        dataclasses.replace(
            timed, policies=dataclasses.replace(
                timed.policies, backend="pallas")).validate()
    with pytest.raises(BackendError) as exc:
        timed.run(backend="pallas")
    msg = str(exc.value)
    assert "events=" in msg
    assert "nearest supported backend: 'reference'" in msg


# ---------------------------------------------------------------------------
# pallas tier: fused kernels in interpret mode (CI: pallas-interpret job)
# ---------------------------------------------------------------------------


def test_pallas_only_auto_resolution_matrix():
    """The :mod:`repro.kernels.ops` resolution matrix for kernels with no
    XLA twin (``pallas_only=True`` — the fabric Pallas kernels): ``auto``
    resolves to ``interpret`` off-TPU, never ``xla``; explicit modes pass
    through unchanged. Pinned off-TPU (the CI case)."""
    from repro.kernels import ops
    if HAVE_JAX and jax.default_backend() == "tpu":
        pytest.skip("matrix below pins the off-TPU resolution")
    saved = ops._BACKEND
    try:
        matrix = {
            # forced:   (pallas_only=False, pallas_only=True)
            "auto": ("xla", "interpret"),
            "pallas": ("pallas", "pallas"),
            "interpret": ("interpret", "interpret"),
            "xla": ("xla", "xla"),
        }
        for forced, (plain, ponly) in matrix.items():
            ops.set_backend(forced)
            assert ops.backend() == plain, forced
            assert ops.backend(pallas_only=True) == ponly, forced
    finally:
        ops._BACKEND = saved


@needs_jax
def test_fabric_kernels_never_interpret_on_a_tpu(monkeypatch):
    """On a TPU the fabric kernels compile or refuse: ``auto``/``pallas``
    lower for the device, and a forced ``xla`` or ``interpret`` raises
    instead of running the interpreter."""
    from repro.fabric.backend.pallas_kernels import interpret_mode
    from repro.kernels import ops
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(ops, "_BACKEND", None)
    for forced in ("auto", "pallas"):
        ops.set_backend(forced)
        assert interpret_mode() is False, forced
    for forced in ("xla", "interpret"):
        ops.set_backend(forced)
        with pytest.raises(BackendError, match="do not interpret"):
            interpret_mode()


@needs_jax
def test_compile_cache_is_placed_from_outside_or_fixed(monkeypatch):
    """``JAX_COMPILATION_CACHE_DIR`` wins and nothing else is set; without
    it the cache sits at ``<repo>/.jax_cache``, the same on every call."""
    import os
    from repro.fabric.backend import use_compile_cache
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    saved = jax.config.jax_compilation_cache_dir
    try:
        jax.config.update("jax_compilation_cache_dir", None)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/set/outside")
        assert use_compile_cache() == "/set/outside"
        assert jax.config.jax_compilation_cache_dir is None
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = use_compile_cache()
        assert path == os.path.join(root, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
        assert use_compile_cache() == path
    finally:
        jax.config.update("jax_compilation_cache_dir", saved)


@needs_jax
def test_pallas_waterfill_specs_block_geometry():
    """The TPU compile path's shape contract, unit-tested without TPU
    hardware: row blocks are sublane-aligned (multiples of 8), capped,
    and rows pad to a whole number of blocks."""
    from repro.fabric.backend.pallas_kernels import (_MAX_BLOCK_ROWS,
                                                    _SUBLANE,
                                                    waterfill_specs)
    for rows, n in [(1, 1), (7, 3), (8, 8), (100, 8), (4096, 8),
                    (4097, 16), (513, 2)]:
        grid, br, padded = waterfill_specs(rows, n)
        assert br % _SUBLANE == 0
        assert br <= max(_MAX_BLOCK_ROWS, _SUBLANE)
        assert padded == grid[0] * br
        assert padded >= rows and padded - rows < br
    # small row counts never over-allocate a full max block
    _, br, padded = waterfill_specs(3, 4)
    assert br == _SUBLANE and padded == _SUBLANE
    # explicit block_rows is honored (aligned up)
    grid, br, padded = waterfill_specs(100, 8, block_rows=30)
    assert br == 32 and padded % 32 == 0
    with pytest.raises(ValueError, match=">= 1"):
        waterfill_specs(0, 4)
    with pytest.raises(ValueError, match=">= 1"):
        waterfill_specs(4, 0)


@needs_jax
@pytest.mark.parametrize("name", ["maxmin_shares", "wfq_shares",
                                  "strict_priority_shares"])
def test_pallas_allocators_bit_exact_under_x64(name):
    """The fused waterfill family at its declared tier: bit-identical to
    the reference Python under float64 (interpret mode on CPU runs the
    same kernel code the TPU lowering compiles)."""
    tier, tol = EQUIVALENCE_TIERS[name]
    assert (tier, tol) == ("exact", 0.0)
    ref = get_kernel(name, KernelType.REFERENCE)
    fast = get_kernel(name, "pallas")
    rng = random.Random(11)
    with jax.enable_x64(True):
        for trial in range(40):
            n = rng.randint(1, 8)
            d = _rand_demands(rng, n)
            cap = rng.choice([0.5, 1.0, 2.0])
            if name == "strict_priority_shares":
                prios = np.array([float(rng.randint(0, 3))
                                  for _ in range(n)])
                want = ref(d, list(prios), cap)
                got = fast(np.array(d), prios, cap)
            elif name == "wfq_shares":
                w = [rng.uniform(0.1, 2.0) for _ in range(n)]
                want = ref(d, w, cap)
                got = fast(np.array(d), np.array(w), cap)
            else:
                want = ref(d, cap)
                got = fast(np.array(d), cap)
            got = np.asarray(got)
            assert got.dtype == np.float64
            assert list(got) == want, (name, trial, d, cap)


@needs_jax
def test_pallas_allocator_edge_cases():
    """Degenerate grids the sweep runner actually produces: zero-demand
    rows, all-saturated links (zero leftover capacity), and the
    single-tenant one-flow row."""
    mm = get_kernel("maxmin_shares", "pallas")
    wfq = get_kernel("wfq_shares", "pallas")
    sp = get_kernel("strict_priority_shares", "pallas")
    with jax.enable_x64(True):
        # zero-demand rows allocate exactly zero and nothing else
        z = np.zeros((3, 4))
        assert np.asarray(mm(z, 1.0)).tolist() == z.tolist()
        assert np.asarray(wfq(z, np.ones(4), 1.0)).tolist() == z.tolist()
        # all-saturated: capacity 0.0 gives everyone exactly 0.0
        d = np.array([[0.5, 1.5, 0.7]])
        assert np.asarray(mm(d, 0.0)).tolist() == [[0.0, 0.0, 0.0]]
        assert np.asarray(
            sp(d, np.array([2.0, 1.0, 0.0]), 0.0)).tolist() \
            == [[0.0, 0.0, 0.0]]
        # oversubscribed link: allocations conserve the full capacity
        big = np.array([[2.0, 3.0, 5.0]])
        out = np.asarray(mm(big, 1.0))
        assert float(out.sum()) == pytest.approx(1.0, abs=0.0)
        # single-tenant degenerate grid: one flow takes min(demand, cap)
        one = np.array([[0.3]])
        assert np.asarray(mm(one, 1.0)).tolist() == [[0.3]]
        assert np.asarray(mm(np.array([[4.0]]), 1.0)).tolist() == [[1.0]]
        # ragged zero-padding stays exact (the runner's batching device)
        d5 = np.array([0.9, 0.1, 1.2, 0.0, 0.0])
        base = np.asarray(mm(d5[:3], 1.0))
        padded = np.asarray(mm(d5, 1.0))
        assert padded[:3].tolist() == base.tolist()
        assert padded[3:].tolist() == [0.0, 0.0]


@needs_jax
@pytest.mark.parametrize("backend", ["reference", "jnp", "pallas"])
def test_pallas_rejection_contract_identical_across_backends(backend):
    """NaN/negative demands or capacity are rejected *before* kernel
    launch with the same ``ValueError`` text on every backend — the
    allocator-boundary contract (`repro.fabric.congestion`)."""
    mm = get_kernel("maxmin_shares", backend)
    bad_d = [0.5, -0.25, 1.0]
    nan_d = [0.5, float("nan")]
    with pytest.raises(ValueError) as exc:
        mm(bad_d if backend == "reference" else np.array(bad_d), 1.0)
    assert str(exc.value) == "demands must be >= 0, got -0.25"
    with pytest.raises(ValueError) as exc:
        mm(nan_d if backend == "reference" else np.array(nan_d), 1.0)
    assert str(exc.value) == "demands must be >= 0, got nan"
    with pytest.raises(ValueError) as exc:
        mm([0.5] if backend == "reference" else np.array([0.5]), -2.0)
    assert str(exc.value) == "capacity must be >= 0, got -2.0"


@needs_jax
def test_pallas_segment_overlap_within_ulp_tier():
    tier, tol = EQUIVALENCE_TIERS["segment_overlap"]
    assert tier == "ulp"
    fast = get_kernel("segment_overlap", "pallas")
    rng = random.Random(17)
    with jax.enable_x64(True):
        for trial in range(40):
            k = rng.randint(1, 12)
            starts = np.array([rng.uniform(0.0, 10.0) for _ in range(k)])
            ends = np.array([s + rng.uniform(-1.0, 4.0) for s in starts])
            for j in range(k):                # empty ring slots: end=-inf
                if rng.random() < 0.25:
                    ends[j] = -np.inf
            s_i = rng.uniform(0.0, 10.0)
            e_i = s_i + rng.uniform(0.0, 5.0)
            want = 0.0
            for s_k, e_k in zip(starts, ends):
                ov = min(e_i, e_k) - max(s_i, s_k)
                if ov > 0.0:
                    want += ov
            got = float(fast(s_i, e_i, starts, ends))
            assert _within_ulps(got, want, tol), (trial, got, want)
        # batched rows match per-row calls bit-for-bit
        S = np.random.default_rng(2).uniform(0.0, 10.0, (6, 9))
        E = S + np.random.default_rng(3).uniform(0.0, 3.0, (6, 9))
        batched = np.asarray(fast(2.0, 7.0, S, E))
        rows = np.array([float(fast(2.0, 7.0, S[i], E[i]))
                         for i in range(6)])
        assert (batched == rows).all()


@needs_jax
@pytest.mark.parametrize("fairness", list(JNP_SCENARIO_FAIRNESS))
def test_scenario_pallas_rtol_tier_under_x64(fairness):
    """`Scenario.run(backend="pallas")` — the scan runner with fused
    allocator/overlap kernels — holds the scenario tier against the
    sequential reference, per fairness mode."""
    tier, tol = EQUIVALENCE_TIERS["scenario"]
    assert tier == "rtol"
    scn = _scenario(fairness)
    ref = scn.run()
    with jax.enable_x64(True):
        fast = scn.run(backend="pallas")
    _series_close(ref, fast, tol)


@needs_jax
def test_grid_pallas_backend_matches_jnp_bits():
    """Pallas and jnp share the scan runner; with bit-exact allocators
    and identical overlap arithmetic the two batched grid runs must be
    bit-identical under float64."""
    from repro.fabric.scenario import ScenarioGrid

    grid = ScenarioGrid(_scenario("wfq"), {
        "congestion.u_mean": [0.2, 0.4],
    })
    with jax.enable_x64(True):
        via_jnp = grid.run(backend="jnp")
        via_pallas = grid.run(backend="pallas")
    for (_, rj), (_, rp) in zip(via_jnp, via_pallas):
        for jname in ("a", "b"):
            assert rj.series(jname) == rp.series(jname)


@needs_jax
def test_policies_backend_pallas_field_selects_pallas():
    from repro.fabric.scenario import Scenario

    scn = _scenario("maxmin", backend="pallas")
    assert Scenario.from_json(scn.to_json()).policies.backend == "pallas"
    via_field = scn.run()
    via_arg = _scenario("maxmin").run(backend="pallas")
    for jname in ("a", "b"):
        assert via_field.series(jname) == via_arg.series(jname)


# ---------------------------------------------------------------------------
# heavier sweep (slow marker; CI backend-equivalence job)
# ---------------------------------------------------------------------------


@pytest.mark.slow
@needs_jax
def test_grid_batched_equivalence_wide_sweep():
    """A wider, longer sweep of the batched runner against the
    sequential reference — every variant, both jobs, float32 bound."""
    import dataclasses

    from repro.fabric.scenario import ScenarioGrid

    base = dataclasses.replace(_scenario("wfq", name="bk-wide"), iters=200,
                               warmup=20)
    grid = ScenarioGrid(base, {
        "congestion.u_mean": [0.15, 0.25, 0.35, 0.45],
        "congestion.k_burst": [0.5, 1.0, 1.5, 2.0],
    })
    results = grid.run(backend="jnp")
    variants = grid.scenarios()
    assert len(results) == 16
    for (params, res), scn in zip(results, variants):
        _series_close(scn.run(), res, 5e-2)


@pytest.mark.slow
@needs_jax
def test_grid_pallas_256_variant_congestion_sweep():
    """The acceptance sweep: 256 congestion variants through
    ``ScenarioGrid.run(backend="pallas")`` as one batched program, held
    to the declared scenario tier against the sequential reference under
    float64 (where the fused allocators are bit-exact, the whole-series
    bound is the tier's rtol)."""
    from repro.fabric.scenario import ScenarioGrid

    tier, tol = EQUIVALENCE_TIERS["scenario"]
    grid = ScenarioGrid(_scenario("wfq", name="bk-pallas-256"), {
        "congestion.u_mean": [0.05 + 0.025 * i for i in range(16)],
        "congestion.k_burst": [0.25 * (i + 1) for i in range(16)],
    })
    with jax.enable_x64(True):
        results = grid.run(backend="pallas")
    variants = grid.scenarios()
    assert len(results) == 256
    for (params, res), scn in zip(results, variants):
        _series_close(scn.run(), res, tol)
