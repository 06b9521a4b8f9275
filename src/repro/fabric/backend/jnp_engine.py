"""Whole-scenario jnp runner: every grid variant as one batched program.

The reference engine steps one scenario at a time in Python; a dense
:class:`~repro.fabric.scenario.ScenarioGrid` therefore pays the
interpreter once per variant per iteration. This module compiles the
engine's iteration loop into a single ``lax.scan`` and ``vmap``s it over
scenario variants, so a 256-point sweep executes as one XLA program
(``bench/run.py`` measures its speed on the chip).

The key structural fact that makes this possible: **every random stream
the engine consumes is feedback-free.** Compute samples
(:class:`~repro.fabric.stragglers.ComputeModel`) and the congestion
AR(1) gaussians depend only on their seeds — never on simulation state —
so both are pregenerated on the host (and cached per seed, amortizing
the host cost across grid variants that share streams) and the scan
body is pure float arithmetic. Pregeneration replays the Python
``random.Random`` stream in bulk through numpy's MT19937: the same
uniform draws at the same positions, the same spike states, and float64
values within 2 ulps of the Python ones (numpy's vector ``exp``/``log``
are not libm's).

What runs where:

  * **Python prep (per variant, cached):** topology build, placement,
    schedule compilation (reusing ``FabricEngine.__init__`` so the node
    sets, seeds, and compiled schedules are exactly the reference
    engine's), stream pregeneration, and schedule encoding into
    ``(stage, entry)`` coefficient matrices.
  * **Traced scan body (per iteration):** arrival windows, the AR(1)
    update, per-link efficiencies, compiled-schedule evaluation,
    co-tenant contention (same-round spans + a busy-segment ring buffer,
    shares via the batched allocators in
    :mod:`repro.fabric.backend.jnp_kernels`), congestion kick, BSP
    finish/step bookkeeping, and the pacing bank.

Deliberate deviations from the reference (why ``scenario`` sits in the
``rtol`` equivalence tier, not ``exact``):

  * float32 by default (float64 under ``jax.enable_x64(True)``). Each
    tenant's arrivals, skew and step are computed relative to its last
    finish, and the absolute clocks that co-tenant windows and busy
    segments are compared on are (whole seconds, fraction) pairs: a
    plain float32 clock at 1e5 s resolves 8 ms, more than a rank's
    compute spread or a window's overlap can afford to lose;
  * the segment store is an unpruned ring buffer per owner. Stale
    segments overlap future windows by <= 0 and clamp to zero (the
    reference's pruning threshold proves the same bound). The scan flags
    any overwrite of a segment the reference would still hold, and
    :func:`run_scenarios` then reruns the group with one slot per
    iteration, so no live segment is ever lost;
  * per-link byte totals are ``iters x bytes_per_call(None)`` — exact
    for ring/tree (static bytes; the reference's repeated adds differ
    only in accumulation rounding), the uncongested-winner approximation
    for hierarchical;
  * per-rank iteration records are not materialized (``trace`` is empty).

Unsupported scenario features raise :class:`BackendError` eagerly:
event/lifecycle timelines, and the ``offered`` / ``drr`` fairness modes
(byte-weighted flows and the data-dependent quantized drain do not
vectorize into the per-owner share call this runner batches). The error
names the offending feature and the nearest backend that supports it.

Per-kernel dispatch: the scan body does not hardcode the jnp kernels —
the allocator family and the segment-overlap reduction are fetched from
the kernel registry for the requested backend (``kernels=`` on
:func:`run_scenarios`), so the same compiled runner serves both
``backend="jnp"`` (:mod:`repro.fabric.backend.jnp_kernels`) and
``backend="pallas"`` (:mod:`repro.fabric.backend.pallas_kernels`, where
the fused waterfill and overlap kernels run via ``pl.pallas_call``).

Telemetry: each host step of a sweep is a ``fabric.*`` span of
:mod:`repro.fabric.telemetry` (the sweep; per variant its preparation,
engine build, encoding, random streams and result; per group the
stacking, the runner call and its launch or build, wait, fetch and
rerun), each cache lookup a hit or miss counter, and each compute-stream
miss the spike-chain steps it resolved one at a time. Telemetry is off
unless enabled, and changes no result.
"""
from __future__ import annotations

import dataclasses
import math
import random
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from repro.fabric import _deprecation, telemetry
from repro.fabric.backend import (JNP_SCENARIO_FAIRNESS, BackendError,
                                  KernelType, get_kernel, register_kernel)
from repro.fabric.backend import jnp_kernels as K
from repro.fabric.congestion import CongestionConfig
from repro.fabric.engine import EngineResult, FabricEngine, JobResult
from repro.fabric.stragglers import ComputeModel

SUPPORTED_FAIRNESS = JNP_SCENARIO_FAIRNESS
SEG_CAPACITY = 64                 # first ring length tried per owner

# -- pregenerated random streams (feedback-free, cached per seed) -----------

_COMPUTE_CACHE: Dict[tuple, np.ndarray] = {}
_GAUSS_CACHE: Dict[tuple, np.ndarray] = {}

# Both streams are replayed through one numpy MT19937, handed the Python
# generator's state on each miss. Its legacy ``random_sample`` is the
# same 53-bit formula as ``random.Random.random``, so it returns the same
# doubles in the same order.
_MT = np.random.RandomState(0)
# draws past a spike-free stream's count, for the heavy-tail draw that
# each spike entry adds; the block grows from the same state past it
HEAVY_MARGIN = 256


def _take_over(rng: random.Random) -> np.random.RandomState:
    words = rng.getstate()[1]      # the 624 state words, then the position
    # a tuple of ints: numpy copies the key word by word, and from a
    # tuple that is some twenty times faster than from an array
    _MT.set_state(("MT19937", words[:624], words[624]))
    return _MT


def _box_muller(x: np.ndarray, y: np.ndarray) -> np.ndarray:
    """The inlined Box-Muller pairs of ``ComputeModel.sample`` and
    ``CongestionModel.advance`` over vectors of their uniforms ``x``,
    ``y``, in the same operation order: cos then sin, interleaved."""
    x2pi = x * (2.0 * math.pi)
    g2rad = np.sqrt(-2.0 * np.log(1.0 - y))
    z = np.empty(2 * x.shape[0])
    z[0::2] = np.cos(x2pi) * g2rad
    z[1::2] = np.sin(x2pi) * g2rad
    return z


def _replay_compute(cfg, n: int, seed: int, iters: int):
    """``iters`` calls of ``ComputeModel(cfg, n, seed).sample()`` drawn in
    bulk. Returns ``(times, spiking, draws, serial)``: the ``(iters, n)``
    samples, the ``(iters, n)`` spike multipliers as ``sample`` leaves
    them in ``spiking`` (0.0 where healthy), the uniforms drawn after the
    locality draws, and how many spike-chain events (entries and exit
    checks) were resolved one at a time.

    Rank-sample ``k = it * n + r`` draws one uniform for its spike check
    (an entry check while healthy, an exit check while spiking), one more
    for the heavy-tail choice on an entry, and on even ``k`` the two
    uniforms of a Box-Muller pair (odd ``k`` uses the cached second
    value). So its check sits at ``2k + (k odd) + E``, ``E`` the entries
    before it. Only the spike chain is serial: entries, found in ``k``
    order among the uniforms below ``spike_prob`` under the current
    shift ``E``, and each spiking rank's exit checks, resolved up to the
    next entry (the shift holds until then). The rest is vector
    arithmetic over the draws less the heavy-tail ones, in ``sample``'s
    operation order."""
    cm = ComputeModel(cfg, n, seed=seed)
    mt = _take_over(cm.rng)
    N = n * iters
    base = N + 2 * ((N + 1) // 2)          # draws with no spike entry
    u = mt.random_sample(base + HEAVY_MARGIN)
    cand = np.flatnonzero(u < cfg.spike_prob).tolist()
    exit_prob = cfg.spike_exit_prob
    heavies: List[int] = []        # buffer positions of heavy-tail draws
    spells: List[tuple] = []       # (first k, end k, multiplier)
    open_: Dict[int, tuple] = {}   # spiking rank -> (first k, next check, m)
    shift = serial = i = 0
    last = -1                      # the last rank-sample resolved

    def exits(upto: int) -> int:
        """Every spiking rank's exit checks below rank-sample ``upto``."""
        steps = 0
        for r, (k0, k, m) in list(open_.items()):
            while k < upto:
                steps += 1
                if u[2 * k + (k & 1) + shift] < exit_prob:
                    spells.append((k0, k, m))
                    del open_[r]
                    break
                k += n
            else:
                open_[r] = (k0, k, m)
        return steps

    while True:
        k = N                          # the next check below spike_prob
        while i < len(cand):
            q = cand[i] - shift
            i += 1
            if q & 3 in (0, 3) and q >> 1 > last:  # a check not yet passed
                k = min(q >> 1, N)
                break
        serial += exits(k)
        if k == N:
            break
        last = k
        if k % n in open_:             # the rank spikes: an exit check
            serial += exits(k + 1)
            continue
        serial += 1
        p = 2 * k + (k & 1) + shift + 1  # the heavy-tail draw
        m = cfg.heavy_mult if u[p] < cfg.heavy_frac else cfg.spike_mult
        heavies.append(p)
        shift += 1
        if u.shape[0] < base + shift:  # the margin ran out: draw on
            more = mt.random_sample(max(HEAVY_MARGIN, u.shape[0] // 4))
            cand += (np.flatnonzero(more < cfg.spike_prob)
                     + u.shape[0]).tolist()
            u = np.concatenate((u, more))
        if m:
            open_[k % n] = (k, k + n, m)
    spells += [(k0, N, m) for k0, _, m in open_.values()]

    # less the heavy-tail draws, each pair of rank-samples draws 4:
    # check, x, y (even k), check (odd k)
    v = np.delete(u[:base + shift], heavies)
    z = _box_muller(v[1::4], v[2::4])[:N]
    times = z.reshape(iters, n) * cfg.jitter_sigma
    np.exp(times, out=times)
    times *= cm._scale
    spiking = np.zeros(N)
    flat = times.reshape(N)
    for k0, k1, m in spells:
        spiking[k0:k1:n] = m
        flat[k0:k1:n] *= m
    return times, spiking.reshape(iters, n), base + shift, serial


def _compute_stream(cfg, n: int, seed: int, iters: int) -> np.ndarray:
    """``ComputeModel.sample`` for ``iters`` iterations, as the reference
    engine consumes it (the model holds no engine-fed state), replayed in
    bulk (:func:`_replay_compute`): the same draws and spike states,
    float64 values within 2 ulps. Cached by (config, n, seed); the stream
    is prefix-stable, so a longer request regenerates once."""
    with telemetry.span("fabric.prep.compute_stream"):
        key = (cfg, n, seed)
        hit = _COMPUTE_CACHE.get(key)
        if hit is None or hit.shape[0] < iters:
            telemetry.count("fabric.compute_stream.miss")
            hit, _, _, serial = _replay_compute(cfg, n, seed, iters)
            telemetry.count("fabric.compute_stream.serial_steps", serial)
            _COMPUTE_CACHE[key] = hit
        else:
            telemetry.count("fabric.compute_stream.hit")
        return hit[:iters]


def _gauss_stream(seed: int, count: int) -> np.ndarray:
    """The congestion AR(1) innovation stream: the engine's inlined
    Box-Muller draws (``CongestionModel.advance``), with the sin/cos pair
    cache carried across ``advance()`` calls, replayed in bulk: the same
    uniforms of ``random.Random(seed)``, float64 values within 2 ulps,
    regardless of how the stream splits across iterations or how
    ``random.gauss`` evolves between Python versions."""
    with telemetry.span("fabric.prep.gauss_stream"):
        key = (seed,)
        hit = _GAUSS_CACHE.get(key)
        if hit is None or hit.shape[0] < count:
            telemetry.count("fabric.gauss_stream.miss")
            u = _take_over(random.Random(seed)).random_sample(
                2 * ((count + 1) // 2))
            _GAUSS_CACHE[key] = hit = _box_muller(u[0::2], u[1::2])[:count]
        else:
            telemetry.count("fabric.gauss_stream.hit")
        return hit[:count]


# -- schedule encoding ------------------------------------------------------


def _encode_schedule(sched, lidx: Dict[str, int], L: int):
    """Freeze a CompiledSchedule into coefficient matrices.

    ``total_s(eff)`` decomposes into stage maxima combined by sum/max
    groups: ring = ``steps * max(entries)``; tree = ``sum_levels
    2 * max(entries)`` (scaling by 2 distributes exactly over the sum);
    hierarchical = ``max_intra_rings(steps_r * max_r) + inter``. Entry
    time is ``num / (bw * eff[link]) + lat`` with unshared links mapped
    to the constant-1.0 efficiency slot ``L``.

    Returns ``(struct, arrays)`` — ``struct`` is the hashable group
    signature (static); ``arrays`` the per-variant float coefficients.
    """
    from repro.fabric.collectives import (_HierSchedule, _RingSchedule,
                                          _SharpSchedule, _TreeSchedule,
                                          _ZeroSchedule)
    stages: List[tuple] = []    # (m:int, entries:[(idx, num, bw, lat)])
    groups: List[Tuple[str, Tuple[int, ...]]] = []

    def add_stage(m: int, plan) -> int:
        if getattr(plan, "spray", ()):
            raise BackendError(
                "jnp backend cannot encode adaptive-spray step plans; "
                "nearest supported backend: 'reference'")
        entries = [(lidx.get(ln, L), num, bw, lat)
                   for (ln, num, bw, lat) in plan.entries]
        stages.append((m, entries))
        return len(stages) - 1

    def add(sched) -> None:
        if isinstance(sched, _ZeroSchedule):
            return
        if isinstance(sched, (_RingSchedule, _SharpSchedule)):
            groups.append(("sum", (add_stage(sched.steps, sched.plan),)))
        elif isinstance(sched, _TreeSchedule):
            groups.append(("sum", tuple(add_stage(2, plan)
                                        for plan in sched.levels)))
        elif isinstance(sched, _HierSchedule):
            if sched.intra:
                groups.append(("max", tuple(
                    add_stage(r.steps, r.plan) for r in sched.intra)))
            add(sched.inter)
        else:
            raise BackendError(
                f"jnp backend cannot encode schedule "
                f"{type(sched).__name__}")

    add(sched)
    S = len(stages)
    E = max((len(e) for _, e in stages), default=0)
    sidx = np.full((S, E), L, dtype=np.int32)
    mask = np.zeros((S, E), dtype=bool)
    num = np.zeros((S, E))
    bw = np.ones((S, E))
    lat = np.zeros((S, E))
    m = np.zeros((S,))
    for s, (mult, entries) in enumerate(stages):
        m[s] = float(mult)
        for e, (li, nm, b, lt) in enumerate(entries):
            sidx[s, e], num[s, e], bw[s, e], lat[s, e] = li, nm, b, lt
            mask[s, e] = True
    struct = (tuple(groups), tuple(tuple(r) for r in sidx), E)
    static = {"sidx": sidx, "mask": mask, "m": m, "groups": groups}
    arrays = {"num": num, "bw": bw, "lat": lat}
    return struct, static, arrays


# -- per-variant prep -------------------------------------------------------


class _Prep:
    __slots__ = ("sig", "static", "data", "scenario", "topo", "jobs",
                 "warmup")


_ENGINE_CACHE: Dict[tuple, tuple] = {}


def _build_jobs(scenario, topo):
    """Topology + placed/compiled job runtimes for a scenario.

    Cached on everything the build actually reads — topology spec, job
    specs, fairness, base_seed (all frozen, hashable dataclasses) — and
    NOT the congestion block, so a grid sweeping congestion floats (the
    common dense sweep) builds its engine exactly once. The cached
    ``_JobRuntime`` objects are never stepped — only their static fields
    (spec, nodes, schedule, spanning, floor_denom, shared_demand) are
    read — so sharing them across variants is safe."""
    if topo is not None:            # hand-built topology: no spec key
        with _deprecation.scenario_scope():
            eng = FabricEngine(topo, list(scenario.jobs),
                               congestion=scenario.congestion,
                               base_seed=scenario.base_seed,
                               fairness=scenario.policies.fairness,
                               routing=scenario.policies.routing)
        return topo, eng._jobs
    key = (scenario.topology, scenario.jobs, scenario.policies.fairness,
           scenario.policies.routing, scenario.base_seed)
    hit = _ENGINE_CACHE.get(key)
    telemetry.count("fabric.engine_cache.miss" if hit is None
                    else "fabric.engine_cache.hit")
    if hit is None:
        topo = scenario.topology.build()
        with _deprecation.scenario_scope():
            eng = FabricEngine(topo, list(scenario.jobs),
                               congestion=scenario.congestion,
                               base_seed=scenario.base_seed,
                               fairness=scenario.policies.fairness,
                               routing=scenario.policies.routing)
        hit = _ENGINE_CACHE[key] = (topo, eng._jobs)
    return hit


def _prep(scenario, topo=None, backend: str = "jnp") -> _Prep:
    with telemetry.span("fabric.prep"):
        if scenario.jobs is None:
            raise BackendError(
                f"backend={backend!r} runs static-jobs scenarios only; "
                f"unsupported feature: events= (lifecycle timeline); "
                f"nearest supported backend: 'reference'")
        fairness = scenario.policies.fairness
        if fairness not in SUPPORTED_FAIRNESS:
            raise BackendError(
                f"backend={backend!r} supports fairness "
                f"{SUPPORTED_FAIRNESS}; unsupported feature: "
                f"fairness={fairness!r}; nearest supported backend: "
                f"'reference'")
        from repro.fabric.policies import ROUTING
        if ROUTING.get(scenario.policies.routing).adaptive:
            raise BackendError(
                f"backend={backend!r} runs static-jobs scenarios only; "
                f"unsupported feature: "
                f"routing={scenario.policies.routing!r} (per-iteration "
                f"byte re-split); nearest supported backend: 'reference'")
        with telemetry.span("fabric.prep.engine"):
            topo, jobs = _build_jobs(scenario, topo)
        with telemetry.span("fabric.prep.encode"):
            return _encode_variant(scenario, topo, jobs)


def _encode_variant(scenario, topo, jobs) -> _Prep:
    """One variant's group signature, static structure and per-variant
    arrays: schedules encoded, random streams drawn (or found cached)."""
    fairness = scenario.policies.fairness
    J = len(jobs)
    iters = scenario.iters
    if topo.sparse_links:
        # match the reference engine's tracked-link insertion order
        # (CongestionModel.track per job) so the gauss stream lines up
        shared = list(dict.fromkeys(
            ln for jr in jobs for ln in jr.shared_demand))
    else:
        shared = [ln for ln, link in topo.links.items() if link.shared]
    lidx = {ln: i for i, ln in enumerate(shared)}
    L = len(shared)
    cc = scenario.congestion if scenario.congestion is not None \
        else CongestionConfig()

    data: Dict[str, np.ndarray] = {}
    sig_jobs = []
    static_jobs = []
    dem = np.zeros((J, L))
    weights = np.zeros(J)
    priorities = np.zeros(J)
    floor = np.zeros(J)
    ecmp = np.zeros(J)
    for j, jr in enumerate(jobs):
        # the engine's compute-seed formula (ComputeModel does not keep it)
        cseed = jr.spec.seed if jr.spec.seed is not None \
            else scenario.base_seed + 1 + 1009 * j
        struct, sstat, sarr = _encode_schedule(jr.schedule, lidx, L)
        data[f"num{j}"] = sarr["num"]
        data[f"bw{j}"] = sarr["bw"]
        data[f"lat{j}"] = sarr["lat"]
        own = tuple(sorted(lidx[ln] for ln in jr.shared_demand))
        for ln, b in jr.shared_demand.items():
            dem[j, lidx[ln]] = b
        weights[j] = jr.spec.weight
        priorities[j] = float(jr.spec.priority)
        floor[j] = jr.floor_denom
        ecmp[j] = 1.0 + cc.ecmp_k * max(0, jr.spanning - 1)
        pc = jr.spec.pacing
        if jr.bank is not None:
            data[f"comp{j}"] = _compute_stream(
                jr.spec.stragglers, jr.n, cseed, iters)
            data[f"pp{j}"] = np.array([
                float(pc.warmup_iters), pc.cv_threshold,
                pc.skew_threshold, pc.gain, pc.decay, pc.max_delay_frac])
            pace_sig = (jr.n, pc.window, bool(pc.enabled))
        else:
            comp = _compute_stream(jr.spec.stragglers, jr.n, cseed, iters)
            data[f"minc{j}"] = comp.min(axis=1)
            data[f"maxc{j}"] = comp.max(axis=1)
            pace_sig = None
        sig_jobs.append((struct, own, pace_sig))
        static_jobs.append({"sched": sstat, "own": np.array(own, np.int32),
                            "pace": pace_sig, "n": jr.n})
    data["dem"] = dem
    data["w"] = weights
    data["floor"] = floor
    data["ecmp"] = ecmp
    data["z"] = _gauss_stream(scenario.base_seed + 2,
                              iters * L).reshape(iters, L) \
        if L else np.zeros((iters, 0))
    data["u0"] = np.full(L, cc.u_mean)
    rho = cc.u_rho
    data["cong"] = np.array([
        rho, (1 - rho) * cc.u_mean, (1 - rho) ** 0.5, cc.u_sigma,
        cc.u_max, cc.k_burst, cc.k_kick])

    prep = _Prep()
    prep.sig = (iters, J, L, fairness, tuple(sig_jobs),
                tuple(priorities.tolist()) if fairness == "strict_priority"
                else None,
                tuple(tuple(row) for row in dem > 0.0))
    prep.static = {"J": J, "L": L, "iters": iters, "fairness": fairness,
                   "jobs": static_jobs, "priorities": priorities,
                   "used": dem > 0.0}
    prep.data = data
    prep.scenario = scenario
    prep.topo = topo
    prep.jobs = jobs
    prep.warmup = scenario.warmup
    return prep


# -- the compiled runner ----------------------------------------------------

_RUNNERS: Dict[tuple, object] = {}


def _relu(x):
    return jnp.where(x > 0.0, x, 0.0)


def _advance(hi, lo, dt):
    """Advance the clock ``hi + lo`` by ``dt``: ``hi`` holds whole seconds
    (exact in float32 up to 2**24 s), ``lo`` the fraction in ``[0, 1)``.
    Both steps after the one add are exact."""
    x = lo + dt
    whole = jnp.floor(x)
    return hi + whole, x - whole


def _since(hi, lo, hi0, lo0):
    """``(hi + lo) - (hi0 + lo0)``, exact to the rounding of the result:
    clocks a window apart differ by a few seconds, not by their size."""
    return (hi - hi0) + (lo - lo0)


def _make_runner(static, kernels: KernelType, S: int):
    J = static["J"]
    L = static["L"]
    iters = static["iters"]
    fairness = static["fairness"]
    sjobs = static["jobs"]
    priorities = static["priorities"]
    used = static["used"]             # (J, L) static link-use mask
    multi = J > 1
    # owners whose segments some co-tenant reads (shares a used link)
    shares = (used.astype(int) @ used.T.astype(int)) > 0
    np.fill_diagonal(shares, False)
    read = shares.any(axis=0)
    others = ~np.eye(J, dtype=bool)
    # registry dispatch: allocators + overlap come from the requested
    # backend (jnp or pallas); the pacing bank stays on the jnp kernel
    # (it has no pallas registration — not one of the two hot paths).
    maxmin_k = get_kernel("maxmin_shares", kernels)
    wfq_k = get_kernel("wfq_shares", kernels)
    sp_k = get_kernel("strict_priority_shares", kernels)
    overlap_k = get_kernel("segment_overlap", kernels)

    def sched_total(j, eff_full, data):
        sd = sjobs[j]["sched"]
        if not sd["groups"]:
            return jnp.zeros(())
        t = data[f"num{j}"] / (data[f"bw{j}"] * eff_full[sd["sidx"]]) \
            + data[f"lat{j}"]
        t = jnp.where(sd["mask"], t, -jnp.inf)
        smax = jnp.maximum(jnp.max(t, axis=1), 0.0) * sd["m"]
        total = None
        for kind, idxs in sd["groups"]:
            if kind == "sum":
                g = smax[idxs[0]]
                for i in idxs[1:]:
                    g = g + smax[i]
            else:                     # max group: first-larger wins
                g = jnp.zeros(())
                for i in idxs:
                    g = jnp.where(smax[i] > g, smax[i], g)
            total = g if total is None else total + g
        return total

    def owner_shares(demands, i, data):
        """Job i's allocator share on each of its links: ``demands`` is
        ``(Lo, J)`` with slot 0 = the owner's unit demand."""
        co = [k for k in range(J) if k != i]
        if fairness == "wfq":
            w = data["w"]
            wvec = jnp.concatenate([w[i:i + 1], w[jnp.array(co)]])
            return wfq_k(demands, wvec)[:, 0]
        if fairness == "strict_priority":
            from repro.fabric.congestion import RESIDUAL_SHARE
            pvec = np.concatenate([[priorities[i]],
                                   [priorities[k] for k in co]])
            share = sp_k(demands, pvec)[:, 0]
            # the policy's starved-class floor (StrictPriorityFairness)
            return jnp.where(share > RESIDUAL_SHARE, share,
                             RESIDUAL_SHARE)
        return maxmin_k(demands)[:, 0]

    def single(data):
        cong = data["cong"]
        rho, drift, iscale, sigma = cong[0], cong[1], cong[2], cong[3]
        u_max, k_burst, k_kick = cong[4], cong[5], cong[6]

        pace0 = []
        for j in range(J):
            if sjobs[j]["pace"] is not None:
                n, w, _ = sjobs[j]["pace"]
                # window buffers, internal delay, release offsets
                pace0.append((jnp.zeros((n, w)), jnp.zeros((n, w)),
                              jnp.zeros((n, w)), jnp.zeros(n),
                              jnp.zeros(n)))
            else:
                pace0.append(None)             # releases at its finish
        # clocks are (whole seconds, fraction) pairs (module docstring);
        # a busy segment is its start pair and its duration
        carry0 = (jnp.asarray(data["u0"]), tuple(pace0),
                  (jnp.zeros(J), jnp.zeros(J)),        # prev finish
                  (jnp.zeros((J, S)), jnp.zeros((J, S)),
                   jnp.full((J, S), -jnp.inf)),        # segments
                  jnp.zeros((), bool))         # a live segment was lost

        def step(carry, xs):
            u, pace, (fin_hi, fin_lo), (seg_hi, seg_lo, seg_d), lost = carry
            t = xs["t"]

            # 1. arrival windows, local to each tenant's last finish:
            # float32 then resolves skew and step however far the
            # absolute clocks have run
            last_loc, skew, arrivals = [], [], []
            for j in range(J):
                if sjobs[j]["pace"] is not None:
                    arr = pace[j][4] + xs[f"comp{j}"]
                    arrivals.append(arr)
                    fj, lj = jnp.min(arr), jnp.max(arr)
                else:
                    arrivals.append(None)
                    fj, lj = xs[f"minc{j}"], xs[f"maxc{j}"]
                last_loc.append(lj)
                skew.append((lj - fj) / data["floor"][j])
            last_hi, last_lo = _advance(fin_hi, fin_lo, jnp.stack(last_loc))

            # 2. AR(1) background congestion
            u = rho * u + drift + iscale * (xs["z"] * sigma)
            u = jnp.clip(u, 0.0, u_max)

            # 3. per-job efficiencies, tentative durations, contention
            effs = []
            for j in range(J):
                burst = 1.0 + k_burst * _relu(skew[j])
                denom = burst * data["ecmp"][j]
                eff = jnp.maximum(1e-3, (1.0 - u) / denom)
                effs.append(jnp.concatenate([eff, jnp.ones(1)]))
            durs0 = [sched_total(j, effs[j], data) for j in range(J)]

            if multi:
                d0_v = jnp.stack(durs0)
                new_effs = []
                for i in range(J):
                    own = sjobs[i]["own"]
                    co = np.array([k for k in range(J) if k != i])
                    co_use = used[co][:, own]               # (J-1, Lo)
                    if own.size == 0 or not co_use.any():
                        new_effs.append(effs[i])
                        continue
                    # co-tenant windows and segments on job i's clock:
                    # its window is [0, d_i)
                    d_i = durs0[i]
                    s_co = _since(last_hi[co], last_lo[co],
                                  last_hi[i], last_lo[i])
                    same = _relu(jnp.minimum(d_i, s_co + d0_v[co])
                                 - jnp.maximum(0.0, s_co))
                    seg_s = _since(seg_hi[co], seg_lo[co],
                                   last_hi[i], last_lo[i])
                    seg = overlap_k(jnp.zeros_like(d_i), d_i, seg_s,
                                    seg_s + seg_d[co])
                    act = jnp.where(jnp.asarray(co_use.T),
                                    (same + seg)[None, :], 0.0)
                    d_safe = jnp.where(d_i > 0.0, d_i, 1.0)
                    dem_co = jnp.minimum(1.0, act / d_safe)
                    demands = jnp.concatenate(
                        [jnp.ones((own.size, 1)), dem_co], axis=1)
                    share = owner_shares(demands, i, data)
                    active = (d_i > 0.0) & (act > 0.0).any(axis=1)
                    share = jnp.where(active, share, 1.0)
                    new_effs.append(
                        effs[i].at[own].set(effs[i][own] * share))
                effs = new_effs
                durs = [sched_total(j, effs[j], data) for j in range(J)]
            else:
                durs = durs0
            durs_v = jnp.stack(durs)
            fin_hi, fin_lo = _advance(last_hi, last_lo, durs_v)
            if multi:
                # record this round's busy segments (ring overwrite —
                # stale entries clamp to zero overlap, no pruning needed)
                slot = jnp.mod(t, S)
                # the reference keeps owner k's segment while it ends
                # after some co-tenant's finish; overwriting one loses it
                past = _since(seg_hi[:, slot, None], seg_lo[:, slot, None],
                              fin_hi[None, :], fin_lo[None, :]) \
                    + seg_d[:, slot, None]          # (owner, co-tenant)
                lost = lost | jnp.any(read & jnp.any(others & (past > 0.0),
                                                     axis=1))
                seg_hi = seg_hi.at[:, slot].set(last_hi)
                seg_lo = seg_lo.at[:, slot].set(last_lo)
                seg_d = seg_d.at[:, slot].set(durs_v)

            # 4. queue-buildup kick, sequential per job
            for j in range(J):
                kk = k_kick * skew[j]
                u_k = u + kk * (1.0 - u)
                u_k = jnp.where(u_k > u_max, u_max, u_k)
                u = jnp.where((k_kick > 0.0) & (skew[j] > 0.0), u_k, u)

            # 5. BSP finish, step series, pacing, release updates
            steps_t, new_pace = [], []
            for j in range(J):
                step_j = last_loc[j] + durs[j]     # finish - prev finish
                steps_t.append(step_j)
                if sjobs[j]["pace"] is None:
                    new_pace.append(None)
                    continue
                n, w, enabled = sjobs[j]["pace"]
                bw_, be_, bs_, delay, rel_off = pace[j]
                col = jnp.mod(t, w)
                wt = last_loc[j] - arrivals[j]
                wt = jnp.where(wt > 0.0, wt, 0.0)
                st = step_j - rel_off                # finish - release
                st = jnp.where(st > 0.0, st, 0.0)
                bw_ = bw_.at[:, col].set(wt)
                be_ = be_.at[:, col].set(wt + delay)
                bs_ = bs_.at[:, col].set(st)
                pp = data[f"pp{j}"]
                delays, delay = K.bank_decide(
                    bw_, bs_, be_, delay, pos=jnp.mod(t + 1, w),
                    count=jnp.minimum(t + 1, w), seen=t + 1,
                    enabled=enabled, warmup_iters=pp[0],
                    cv_threshold=pp[1], skew_threshold=pp[2],
                    gain=pp[3], decay=pp[4], max_delay_frac=pp[5])
                new_pace.append((bw_, be_, bs_, delay, delays))

            carry = (u, tuple(new_pace), (fin_hi, fin_lo),
                     (seg_hi, seg_lo, seg_d), lost)
            return carry, jnp.stack(steps_t)

        xs = {"t": jnp.arange(iters), "z": jnp.asarray(data["z"])}
        for j in range(J):
            for k in (f"comp{j}", f"minc{j}", f"maxc{j}"):
                if k in data:
                    xs[k] = jnp.asarray(data[k])
        carry, steps = lax.scan(step, carry0, xs)
        return steps, carry[-1]        # (iters, J), ring overflowed

    return jax.jit(jax.vmap(single))


def _get_runner(sig, static, kernels: KernelType, S: int):
    key = (sig, kernels, S, bool(jax.config.jax_enable_x64))
    fn = _RUNNERS.get(key)
    if fn is None:
        telemetry.count("fabric.runner.build")
        for job in static["jobs"]:
            if job["pace"] is not None and job["pace"][2]:
                telemetry.count("fabric.runner.median_network")
        fn = _RUNNERS[key] = _make_runner(static, kernels, S)
    return fn


def _launch(sig, static, kernels: KernelType, S: int, data):
    """Look the runner up and launch it on ``data``: the call moves the
    stacked host arrays to the device and enqueues the program. The first
    call of a runner just built also traces and compiles it (or loads it
    from the persistent cache): that call is ``fabric.runner.build``."""
    known = len(_RUNNERS)
    fn = _get_runner(sig, static, kernels, S)
    built = len(_RUNNERS) > known
    with telemetry.span("fabric.runner.build" if built
                        else "fabric.runner.launch"):
        return fn(data)


def _run_group(static, sig, data, kernels: KernelType) -> np.ndarray:
    """Run one structural group on a ``SEG_CAPACITY`` ring; if any
    variant overwrote a live segment, run it again with a ring as long
    as the run, which never overwrites one."""
    with telemetry.span("fabric.runner"):
        iters = static["iters"]
        S = min(SEG_CAPACITY, iters)
        steps, lost = _launch(sig, static, kernels, S, data)
        with telemetry.span("fabric.runner.wait"):
            lost = np.asarray(lost)
        if S < iters and lost.any():
            telemetry.count("fabric.runner.rerun")
            with telemetry.span("fabric.runner.rerun"):
                steps, _ = _launch(sig, static, kernels, iters, data)
                steps.block_until_ready()
        with telemetry.span("fabric.runner.fetch"):
            return np.asarray(steps)


# -- result assembly --------------------------------------------------------


def _wrap(prep: _Prep, steps: np.ndarray):
    """Build the standard Result shape from the scan output. Per-link
    byte totals are ``iters x bytes_per_call(None)`` (see module
    docstring); traces are empty (no per-rank record matrices)."""
    from repro.fabric.scenario import Result
    with telemetry.span("fabric.wrap"):
        iters = prep.scenario.iters
        job_results = []
        fabric: Dict[str, float] = {}
        for j, jr in enumerate(prep.jobs):
            series = [float(x) for x in steps[prep.warmup:, j]]
            link_bytes = {ln: iters * b for ln, b
                          in jr.schedule.bytes_per_call(None).items()}
            for ln, b in link_bytes.items():
                fabric[ln] = fabric.get(ln, 0.0) + b
            job_results.append(JobResult(jr.spec, jr.nodes, series,
                                         link_bytes, [], algo=jr.algo))
        raw = EngineResult(topo=prep.topo, jobs=job_results,
                           link_bytes=fabric)
        return Result(prep.scenario, raw, prep.topo)


def run_scenarios(items: Sequence[Tuple[object, Optional[object]]],
                  kernels: KernelType = KernelType.JNP) -> List[object]:
    """Run ``(scenario, topo-or-None)`` pairs on the batched runner.

    Variants are grouped by structural signature (topology link
    structure, job count/placement/schedule shape, fairness, pacing
    windows, iteration count); each group compiles once and executes as
    one vmapped program. Results come back in input order. ``kernels``
    picks which registry backend serves the allocator and
    segment-overlap calls inside the scan body (``KernelType.JNP`` or
    ``KernelType.PALLAS``).
    """
    kernels = KernelType.parse(kernels, default=KernelType.JNP)
    with telemetry.span("fabric.sweep"):
        preps = [_prep(s, t, backend=kernels.value) for s, t in items]
        with telemetry.span("fabric.stack"):
            groups: Dict[tuple, List[int]] = {}
            for i, p in enumerate(preps):
                groups.setdefault(p.sig, []).append(i)
        results: List[object] = [None] * len(preps)
        for sig, idxs in groups.items():
            with telemetry.span("fabric.stack"):
                static = preps[idxs[0]].static
                data = {k: np.stack([preps[i].data[k] for i in idxs])
                        for k in preps[idxs[0]].data}
            out = _run_group(static, sig, data, kernels)
            for b, i in enumerate(idxs):
                results[i] = _wrap(preps[i], out[b])
        return results


@register_kernel("scenario", KernelType.JNP)
def run_scenario(scenario, topo=None):
    """Single-scenario front door (``Scenario.run(backend="jnp")``)."""
    return run_scenarios([(scenario, topo)])[0]
