"""Compile the fabric hot path for a TPU v5e that is described, not attached.

The TPU compiler is installed with JAX, so these tests lower and compile
the Pallas kernels and the Pallas-backed scan runner for a ``v5e:2x2``
topology without a chip. They catch what interpret mode cannot: a
primitive with no TPU lowering, a block the tiling rejects, a kernel
that uses too much fast memory. Nothing runs, so they say nothing about
results or times.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports this file.
"""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.fabric.backend import KernelType, get_kernel

N_TENANTS = 8            # flows per waterfill row
ROWS = 8                 # owned links, padded to the sublane count
SEGS = 64                # busy-segment ring slots per owner


@pytest.fixture(scope="module")
def one_chip():
    """One device of a described v5e:2x2, with the persistent compile
    cache off (an entry compiled here cannot be read back without a chip)
    and the fabric kernels steered to the real Pallas lowering."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    from repro.kernels import ops

    saved_log = os.environ.get("TPU_LOG_DIR")
    saved_cache = jax.config.jax_enable_compilation_cache
    saved_backend = ops._BACKEND
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    ops.set_backend("pallas")
    try:
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — any failure means no desc
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        ops._BACKEND = saved_backend
        jax.config.update("jax_enable_compilation_cache", saved_cache)
        if saved_log is None:
            os.environ.pop("TPU_LOG_DIR", None)
        else:
            os.environ["TPU_LOG_DIR"] = saved_log


def _f32(sharding, *shape):
    return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)


def _kernel_case(name):
    """(callable, argument shapes) for one kernel at the runner's shapes."""
    k = get_kernel(name, KernelType.PALLAS)
    prios = np.arange(N_TENANTS) % 3
    return {
        "maxmin_shares": (lambda d: k(d), [(ROWS, N_TENANTS)]),
        "wfq_shares": (lambda d, w: k(d, w), [(ROWS, N_TENANTS),
                                              (N_TENANTS,)]),
        "strict_priority_shares": (lambda d: k(d, prios),
                                   [(ROWS, N_TENANTS)]),
        "segment_overlap": (lambda s, e, ss, ee: k(s, e, ss, ee),
                            [(), (), (N_TENANTS - 1, SEGS),
                             (N_TENANTS - 1, SEGS)]),
    }[name]


@pytest.mark.parametrize("name", ["maxmin_shares", "wfq_shares",
                                  "strict_priority_shares",
                                  "segment_overlap"])
def test_fabric_kernel_compiles_for_v5e(one_chip, name):
    fn, shapes = _kernel_case(name)
    args = [_f32(one_chip, *s) for s in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_waterfill_vmapped_over_256_variants_compiles(one_chip):
    fn, shapes = _kernel_case("wfq_shares")
    args = [_f32(one_chip, 256, *s) for s in shapes]
    compiled = jax.jit(jax.vmap(fn)).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("fairness", ["maxmin", "wfq", "strict_priority"])
def test_pallas_runner_compiles_for_v5e(one_chip, fairness):
    """The scan runner of a 4-tenant, 64-variant grid with its allocator
    and overlap calls on the Pallas kernels, as ``ScenarioGrid.run(
    backend="pallas")`` builds it."""
    from repro.fabric.backend import jnp_engine
    from repro.fabric.congestion import CongestionConfig
    from repro.fabric.engine import JobSpec
    from repro.fabric.scenario import (Policies, Scenario, ScenarioGrid,
                                       TopologySpec)

    base = Scenario(
        name="chip-compile",
        topology=TopologySpec(n_nodes=32, nodes_per_leaf=8),
        jobs=[JobSpec(f"t{j}", 8, placement="striped",
                      grad_bytes=2e9 * (1 + j % 3), weight=1.0 + j,
                      priority=j % 3) for j in range(4)],
        congestion=CongestionConfig(k_kick=0.25),
        policies=Policies(fairness=fairness), iters=64, warmup=8)
    grid = ScenarioGrid(base, {
        "congestion.u_mean": [0.15 + 0.05 * i for i in range(8)],
        "congestion.k_burst": [0.25 * (i + 1) for i in range(8)]})
    preps = [jnp_engine._prep(s) for s in grid.scenarios()]
    assert len(preps) == 64 and len({p.sig for p in preps}) == 1
    runner = jnp_engine._make_runner(preps[0].static, KernelType.PALLAS,
                                     jnp_engine.SEG_CAPACITY)
    data = {k: _f32(one_chip, len(preps), *np.shape(v))
            for k, v in preps[0].data.items()}
    compiled = runner.lower(data).compile()
    assert "tpu_custom_call" in compiled.as_text()
