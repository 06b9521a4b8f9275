"""The batched engine's random streams against the Python draws they
replay (:mod:`repro.fabric.backend.jnp_engine`).

``_compute_stream`` and ``_gauss_stream`` draw ``random.Random``'s
MT19937 stream in bulk through numpy. The contract: every uniform draw
at the same position, every spike state and multiplier equal, and each
float64 value within 2 ulps of the Python loop's (numpy's vector
``exp``/``log`` are not libm's). The Python loops are the references:
``ComputeModel.sample`` for the compute stream, and below the inlined
Box-Muller loop the congestion model draws for the gaussian stream."""
import math
import random

import numpy as np
import pytest

from repro.fabric.backend import jnp_engine as E
from repro.fabric.simulator import SimConfig
from repro.fabric.stragglers import ComputeModel, StragglerConfig

TABLE1 = SimConfig.paper(64, coordination=False).stragglers
HEAVY = StragglerConfig(spike_prob=0.3, spike_exit_prob=0.05, heavy_frac=0.5)


def _within_ulps(got, want, n_ulps):
    bound = n_ulps * np.spacing(np.maximum(np.abs(got), np.abs(want)))
    return bool(np.all(np.abs(got - want) <= bound))


def _sampled(cfg, n, seed, iters):
    """``iters`` samples of the Python model, its spike multipliers after
    each, and the model (its generator where ``sample`` left it)."""
    cm = ComputeModel(cfg, n, seed=seed)
    times, states = [], []
    for _ in range(iters):
        times.append(cm.sample())
        states.append(list(cm.spiking))
    return np.array(times), np.array(states), cm


@pytest.mark.parametrize("cfg,n,iters,shorter_first", [
    (TABLE1, 64, 400, None),
    (StragglerConfig(), 64, 200, None),
    (HEAVY, 16, 100, None),
    (StragglerConfig(spike_prob=0.0), 8, 50, None),
    (StragglerConfig(spike_prob=0.05, heavy_frac=0.0), 8, 80, None),
    (StragglerConfig(spike_prob=0.05), 7, 91, None),
    (StragglerConfig(spike_prob=0.05), 1, 300, None),
    (TABLE1, 64, 1, None),
    (HEAVY, 7, 60, 25),
    (HEAVY, 64, 200, None),            # more entries than HEAVY_MARGIN
], ids=["table1", "defaults", "spike_heavy", "no_spikes", "no_heavy",
        "odd_n", "one_rank", "one_iter", "longer_after_shorter",
        "margin_exhausted"])
def test_compute_stream_replays_sample(cfg, n, iters, shorter_first):
    seed = 91_000 + 17 * n + iters
    E._COMPUTE_CACHE.pop((cfg, n, seed), None)
    if shorter_first:
        prefix = E._compute_stream(cfg, n, seed, shorter_first).copy()
    got = E._compute_stream(cfg, n, seed, iters)
    times, spiking, draws, _ = E._replay_compute(cfg, n, seed, iters)
    want, states, cm = _sampled(cfg, n, seed, iters)

    assert got.shape == (iters, n) and got.dtype == np.float64
    assert np.array_equal(got, times)
    if shorter_first:
        assert np.array_equal(got[:shorter_first], prefix)
    assert np.array_equal(spiking, states)
    assert _within_ulps(times, want, 2)
    # the same number of draws: the generator ends where sample() left it
    after = random.Random(seed)
    for _ in range(n + draws):
        after.random()
    assert after.getstate()[1] == cm.rng.getstate()[1]
    if cfg is HEAVY and n == 64:
        base = 2 * n * iters
        assert draws - base > E.HEAVY_MARGIN


def _gauss_loop(seed, count):
    rnd = random.Random(seed).random
    out, g_next = [], None
    for _ in range(count):
        z = g_next
        if z is None:
            x2pi = rnd() * (2.0 * math.pi)
            g2rad = math.sqrt(-2.0 * math.log(1.0 - rnd()))
            z = math.cos(x2pi) * g2rad
            g_next = math.sin(x2pi) * g2rad
        else:
            g_next = None
        out.append(z)
    return np.array(out, dtype=np.float64)


@pytest.mark.parametrize("count", [0, 1, 2, 7, 640, 4001])
def test_gauss_stream_replays_box_muller_loop(count):
    seed = 93_000 + count
    E._GAUSS_CACHE.pop((seed,), None)
    got = E._gauss_stream(seed, count)
    assert got.shape == (count,) and got.dtype == np.float64
    assert _within_ulps(got, _gauss_loop(seed, count), 2)


@pytest.mark.parametrize("seed,skip", [(0, 0), (2**31 + 5, 3),
                                       (123_456_789_012, 700)])
def test_mt19937_hand_off_draws_the_python_doubles(seed, skip):
    rng = random.Random(seed)
    for _ in range(skip):
        rng.random()
    bulk = E._take_over(rng).random_sample(5000)
    assert np.array_equal(bulk, [rng.random() for _ in range(5000)])
