"""Bring-up smoke: the compiled fabric sweep, its Pallas kernels and the
advisor, run once on a TPU through the entry points a user calls.

Run it from the repository root on a machine with one TPU::

    python chip_smoke.py

It takes no options. Phases, in order, each printing one line:

  device          the first device must be a TPU, and the fabric kernels
                  must resolve to the real Pallas lowering;
  dense_sweep     the 256-variant congestion grid of
                  ``benchmarks/backend_bench.py`` on ``backend="jnp"``,
                  cold then warm, with 12 evenly spaced variants checked
                  per iteration against the reference engine;
  cotenant_sweep  eight 64-rank striped tenants on a 512-node fat tree,
                  192 variants (fairness x u_mean x k_burst) on ``jnp``
                  and on ``pallas``; the Pallas program must hold the
                  kernels, and both backends must agree with each other
                  and, on one variant per fairness mode, with the
                  reference engine;
  advise          ``advise(..., backend="pallas")`` on the
                  ``topology_contention`` library scenario.

The last line is one JSON object naming the device. The script stops
with a non-zero exit at the first failure, and when JAX finds no TPU.

Eight co-tenants striped over every leaf make the step dynamics
sensitive to rounding: under ``maxmin`` and ``wfq`` the reference engine
run against its twin, the same scenario with ``u_mean`` moved by one
float32 epsilon, departs by more than the bound within a few dozen
iterations and never returns. No float32 run can follow the reference
step for step past that horizon. So the co-tenant phase runs its grid
with no warm-up (the runs are the same; only the reported series
differs), finds the horizon per fairness mode from the twin, and holds
the batched series to the bound per iteration over the first half of
it: the whole run where the twin never departs. Over the steady window
(iterations after ``warmup``) each tenant's mean step is held to the
bound too. Pallas and jnp are held to each other per iteration over the
whole grid. The single-tenant dense sweep is held per iteration.
"""
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import numpy as np  # noqa: E402

# The float32 bound the test suite holds the batched runner to against
# the float64 reference (tests/test_backend.py).
BOUND = 5e-2
FAIRNESS = ("maxmin", "wfq", "strict_priority")

# What the run requires and how large it is. A test rehearses the script
# on the CPU by replacing this dict; the command line has no options.
SIZE = {
    "platform": "tpu",
    "dense_axes": None,             # None: backend_bench.AXES, 256 variants
    "dense_iters": 400, "dense_warmup": 40, "dense_checked": 12,
    "nodes": 512, "tenants": 8, "ranks": 64,
    "iters": 400, "warmup": 40,
    "u_mean": [0.15, 0.2, 0.25, 0.3, 0.35, 0.4, 0.45, 0.5],
    "k_burst": [0.25, 0.5, 0.75, 1.0, 1.25, 1.5, 1.75, 2.0],
    "checked_u_mean": 0.3, "checked_k_burst": 1.0,
}


class SmokeFailure(RuntimeError):
    """A phase found the system wrong."""


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def _timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0


def _errors(want, got, skip: int = 0):
    """Relative error of ``got`` against ``want``: per iteration as an
    ``(iterations, tenants)`` array, and the worst per-tenant mean over
    the iterations after ``skip``."""
    a, b = ([np.asarray(r.series(job.name), dtype=np.float64)
             for job in want.scenario.jobs] for r in (want, got))
    a, b = np.stack(a, axis=1), np.stack(b, axis=1)
    check(a.shape == b.shape and a.size > 0,
          f"series shapes {a.shape} and {b.shape}")
    check(np.isfinite(b).all(), "non-finite steps")
    mean = np.abs(b[skip:].mean(axis=0) / a[skip:].mean(axis=0) - 1.0)
    return np.abs(b - a) / a, float(mean.max())


def _horizon(err) -> int:
    """First iteration at which some tenant is off by more than the
    bound; the run length if none is."""
    off = np.nonzero((err > BOUND).any(axis=1))[0]
    return int(off[0]) if off.size else len(err)


def phase_device():
    import jax
    from repro.kernels import ops

    dev = jax.devices()[0]
    check(dev.platform == SIZE["platform"],
          f"device platform is {dev.platform!r}, not {SIZE['platform']!r}")
    kernels = ops.backend(pallas_only=True)
    want = "pallas" if SIZE["platform"] == "tpu" else "interpret"
    check(kernels == want, f"fabric kernels resolve to {kernels!r}")
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(jax.devices())} fabric_kernels={kernels}",
          flush=True)
    return dev


def phase_dense():
    from benchmarks import backend_bench
    from repro.fabric.scenario import ScenarioGrid

    bench = backend_bench._grid()
    grid = ScenarioGrid(
        dataclasses.replace(bench.base, iters=SIZE["dense_iters"],
                            warmup=SIZE["dense_warmup"]),
        SIZE["dense_axes"] or backend_bench.AXES)
    n = len(grid)
    _, cold = _timed(lambda: grid.run(backend="jnp"))
    results, warm = _timed(lambda: grid.run(backend="jnp"))
    k = SIZE["dense_checked"]
    sample = list(range(0, n, max(1, n // k)))[:k]
    worst = 0.0
    scenarios = grid.scenarios()
    for i in sample:
        err, _ = _errors(scenarios[i].run(), results[i][1])
        worst = max(worst, float(err.max()))
    check(worst <= BOUND, f"dense sweep: worst per-iteration relative "
                          f"error {worst!r} > {BOUND}")
    print(f"dense_sweep: variants={n} iters={SIZE['dense_iters']} "
          f"cold_s={cold!r} (set-up: compile + first run) warm_s={warm!r} "
          f"reference_variants={len(sample)} worst_rel_iter={worst!r}",
          flush=True)


def _cotenant_grid():
    from repro.fabric.congestion import CongestionConfig
    from repro.fabric.engine import JobSpec
    from repro.fabric.scenario import Scenario, ScenarioGrid, TopologySpec

    base = Scenario(
        name="cotenant",
        topology=TopologySpec(n_nodes=SIZE["nodes"], nodes_per_leaf=8),
        jobs=[JobSpec(f"t{j}", SIZE["ranks"], placement="striped",
                      grad_bytes=2e9 * (1 + j % 3), weight=1.0 + j,
                      priority=j % 3) for j in range(SIZE["tenants"])],
        congestion=CongestionConfig(k_kick=0.25),
        iters=SIZE["iters"], warmup=0)
    return ScenarioGrid(base, {"policies.fairness": list(FAIRNESS),
                               "congestion.u_mean": SIZE["u_mean"],
                               "congestion.k_burst": SIZE["k_burst"]})


def _pallas_programs(scenarios):
    """Compiled text of every Pallas-backed runner the sweep used, one
    per fairness group (the persistent cache serves the compile)."""
    import jax
    import jax.numpy as jnp
    from repro.fabric.backend import KernelType, jnp_engine

    texts = []
    for fairness in FAIRNESS:
        group = [s for s in scenarios if s.policies.fairness == fairness]
        prep = jnp_engine._prep(group[0])
        shapes = {k: jax.ShapeDtypeStruct((len(group),) + np.shape(v),
                                          jnp.float32)
                  for k, v in prep.data.items()}
        runners = [fn for (sig, kernels, _, _), fn
                   in jnp_engine._RUNNERS.items()
                   if sig == prep.sig and kernels is KernelType.PALLAS]
        check(runners, f"no Pallas runner ran for fairness={fairness!r}")
        texts += [fn.lower(shapes).compile().as_text() for fn in runners]
    return texts


def phase_cotenant(dev):
    grid = _cotenant_grid()
    scenarios = grid.scenarios()
    n = len(grid)
    _, jnp_cold = _timed(lambda: grid.run(backend="jnp"))
    via_jnp, jnp_warm = _timed(lambda: grid.run(backend="jnp"))
    _, pallas_cold = _timed(lambda: grid.run(backend="pallas"))
    via_pallas, pallas_warm = _timed(lambda: grid.run(backend="pallas"))

    if SIZE["platform"] == "tpu":
        for text in _pallas_programs(scenarios):
            check("tpu_custom_call" in text,
                  "the Pallas runner holds no tpu_custom_call")

    pj_iter = 0.0
    for (_, rj), (_, rp) in zip(via_jnp, via_pallas):
        pj_iter = max(pj_iter, float(_errors(rj, rp)[0].max()))
    check(pj_iter <= BOUND, f"pallas vs jnp: worst per-iteration relative "
                            f"error {pj_iter!r} > {BOUND}")

    skip = SIZE["warmup"]
    checks, ref_s = [], 0.0
    for fairness in FAIRNESS:
        i = next(i for i, (p, _) in enumerate(grid)
                 if p["policies.fairness"] == fairness
                 and p["congestion.u_mean"] == SIZE["checked_u_mean"]
                 and p["congestion.k_burst"] == SIZE["checked_k_burst"])
        scn = scenarios[i]
        twin = dataclasses.replace(scn, congestion=dataclasses.replace(
            scn.congestion, u_mean=scn.congestion.u_mean
            * (1.0 + float(np.finfo(np.float32).eps))))
        (ref, twin_res), t = _timed(lambda: (scn.run(), twin.run()))
        ref_s += t
        twin_err, twin_mean = _errors(ref, twin_res, skip)
        horizon = _horizon(twin_err)
        span = horizon if horizon == len(twin_err) else horizon // 2
        worst_iter = worst_mean = 0.0
        for backend, res in (("jnp", via_jnp[i][1]),
                             ("pallas", via_pallas[i][1])):
            err, mean = _errors(ref, res, skip)
            check(_horizon(err) >= span,
                  f"{fairness} on {backend}: step {_horizon(err)} is off "
                  f"the reference by more than {BOUND}, inside the "
                  f"checked span of {span} steps (twin horizon {horizon})")
            check(mean <= BOUND, f"{fairness} on {backend}: tenant-mean "
                                 f"relative error {mean!r} > {BOUND}")
            worst_iter = max(worst_iter, float(err[:span].max()))
            worst_mean = max(worst_mean, mean)
        checks.append(f"{fairness}:twin_horizon={horizon},checked={span},"
                      f"worst_rel_iter={worst_iter!r},"
                      f"worst_rel_mean={worst_mean!r},"
                      f"twin_rel_mean={twin_mean!r}")

    stats = dev.memory_stats() or {}
    print(f"cotenant_sweep: variants={n} tenants={SIZE['tenants']} "
          f"ranks={SIZE['ranks']} nodes={SIZE['nodes']} "
          f"iters={SIZE['iters']} jnp_cold_s={jnp_cold!r} "
          f"jnp_warm_s={jnp_warm!r} pallas_cold_s={pallas_cold!r} "
          f"pallas_warm_s={pallas_warm!r} pallas_vs_jnp_iter={pj_iter!r} "
          f"reference_s={ref_s!r} "
          f"peak_bytes_in_use={stats.get('peak_bytes_in_use')} "
          f"vs_reference {' '.join(checks)}", flush=True)


def phase_advise():
    from repro.fabric.advisor import advise
    from repro.fabric.backend import batched_eligible
    from repro.fabric.scenario import library

    recs, t = _timed(lambda: advise(library.build("topology_contention"),
                                    backend="pallas"))
    check(recs, "advise returned no recommendations")
    for r in recs:
        if batched_eligible(r.scenario):
            check(r.backend == "pallas",
                  f"{r.action!r} came from {r.backend!r}, not pallas")
    verified = [r for r in recs if r.verified_delta_s is not None
                and r.backend != "reference"]
    check(verified, "advise verified no batched prediction")
    for r in verified:
        check(np.sign(r.verified_delta_s) == np.sign(r.predicted_delta_s),
              f"{r.action!r}: predicted {r.predicted_delta_s!r}, "
              f"verified {r.verified_delta_s!r}")
    print(f"advise: recommendations={len(recs)} "
          f"pallas={sum(r.backend == 'pallas' for r in recs)} "
          f"verified={len(verified)} seconds={t!r} "
          f"top={recs[0].action!r}", flush=True)


def main() -> None:
    import jax

    dev = phase_device()
    phase_dense()
    phase_cotenant(dev)
    phase_advise()
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)


if __name__ == "__main__":
    from repro.fabric.backend import use_compile_cache
    use_compile_cache()
    main()
