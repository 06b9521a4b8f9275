"""Sharding-rule unit tests + hypothesis property tests for system
invariants (divisibility fallback, quantization bounds, pacing bounds,
elastic mesh plans)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip(
    "hypothesis",
    reason="property tests need hypothesis (see requirements-dev.txt)")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.configs.base import PacingConfig
from repro.core.pacing import PacingController
from repro.ft import plan_elastic_mesh
from repro.launch import sharding as shd
from repro.optim import quantize_roundtrip


@pytest.fixture()
def mesh():
    return jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)


def test_resolve_spec_basic(mesh):
    with shd.axis_rules(mesh):
        p = shd.resolve_spec((8, 16), ("batch", "heads"))
        assert p == jax.sharding.PartitionSpec(("data",), "model")


def test_resolve_spec_fallback_records(mesh):
    with shd.axis_rules(mesh):
        shd.resolve_spec((7,), ("heads",))   # 7 % 1 == 0 on 1-dev mesh: ok
        # simulate a 16-way model axis via a fake rule on data axis of size 1
    big = jax.make_mesh((1, 1), ("data", "model"),
                        axis_types=(jax.sharding.AxisType.Auto,) * 2)
    with shd.axis_rules(big):
        spec = shd.resolve_spec((8,), ("ff",))
        assert spec == jax.sharding.PartitionSpec("model")


def test_logical_identity_without_rules():
    x = jnp.ones((2, 3))
    assert shd.logical(x, "batch", None) is x


@settings(max_examples=200, deadline=None)
@given(dim=st.integers(1, 4096))
def test_fallback_divisibility_invariant(dim):
    """resolve_spec never assigns axes whose product doesn't divide the dim.

    (Uses the rule table against a virtual 16-way axis by checking the
    arithmetic helper directly — the live mesh here has 1 device.)
    """
    # arithmetic core of the fallback: drop trailing axes until divisible
    sizes = {"model": 16, "data": 16, "pod": 2}
    phys = ["pod", "data"]
    div = 32
    while phys and dim % div != 0:
        dropped = phys.pop()
        div //= sizes[dropped]
    assert div in (1, 2, 32)
    assert dim % div == 0


@settings(max_examples=100, deadline=None)
@given(st.lists(st.floats(-1e4, 1e4, allow_nan=False), min_size=1,
                max_size=2048).map(np.asarray))
def test_quantize_roundtrip_property(xs):
    x = jnp.asarray(xs, jnp.float32)
    q = quantize_roundtrip(x)
    block_max = float(jnp.max(jnp.abs(x)))
    assert float(jnp.max(jnp.abs(q - x))) <= block_max / 127.0 + 1e-4


@settings(max_examples=50, deadline=None)
@given(waits=st.lists(st.floats(0, 10, allow_nan=False), min_size=1,
                      max_size=200),
       steps=st.lists(st.floats(0.01, 10, allow_nan=False), min_size=1,
                      max_size=200))
def test_pacing_always_bounded_property(waits, steps):
    cfg = PacingConfig(window=8, max_delay_frac=0.5, warmup_iters=2)
    c = PacingController(cfg)
    n = min(len(waits), len(steps))
    meds = []
    for w, s in zip(waits[:n], steps[:n]):
        c.observe(w, s)
        meds.append(s)
        d = c.decide()
        med = sorted(c._steps)[len(c._steps) // 2]
        assert d.delay >= 0.0
        assert d.delay <= cfg.max_delay_frac * med + 1e-9


@settings(max_examples=100, deadline=None)
@given(n=st.integers(1, 4096))
def test_elastic_mesh_plan_property(n):
    shape, axes = plan_elastic_mesh(n, model_parallel=16)
    used = 1
    for s in shape:
        used *= s
    assert used <= n
    assert len(shape) == len(axes)
    # model axis preserved whenever possible
    if n >= 16:
        assert shape[-1] == 16
