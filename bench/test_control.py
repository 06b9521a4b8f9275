"""The checks that decide ``correct`` fail what they must, at test scale.

* The control, the plain reference computed in bfloat16 (the precision
  below the float32 the configurations state), reads above every cell's
  limit.
* A run whose timed path is broken underneath, with the harness's look
  for a chip skipped, comes out not correct: once per fault the cells
  can have.
"""
import numpy as np
import pytest

from bench import cells, check
from bench.test_harness import SHRINK, run_small


@pytest.mark.parametrize("name", sorted(SHRINK))
def test_control_reads_above_the_limit(name):
    spec = cells.cell(name)
    scn, _ = SHRINK[name](spec["config_data"]["scenario"],
                          spec["traffic_data"])
    scn["iters"] = 40
    limits = spec["limits"]
    worst = 0.0
    for seed in (3, 4, 5):
        scn["base_seed"] = seed
        want, _, span = check.reference_span(scn, limits["departure"])
        assert span > 0
        worst = max(worst, check.worst_rel(check.control_rows(scn, span),
                                           want))
    assert worst > limits["limits"]["worst_rel_iter"]


FAULTS = ("state_unchanged", "half_batch", "answer_altered")


@pytest.mark.parametrize("fault", FAULTS)
@pytest.mark.parametrize("name", sorted(SHRINK))
def test_broken_timed_path_is_not_correct(name, fault, monkeypatch):
    from repro.fabric.backend import jnp_engine
    run_group = jnp_engine._run_group
    spec = cells.cell(name)
    warmup = SHRINK[name](spec["config_data"]["scenario"],
                          spec["traffic_data"])[0]["warmup"]

    def broken(static, sig, data, kernels):
        if fault == "half_batch":               # half the variants run;
            B = len(next(iter(data.values())))  # the rest copy them
            half = max(1, B // 2)
            steps = run_group(static, sig, {k: v[:half]
                                            for k, v in data.items()},
                              kernels)
            return np.concatenate([steps, steps[:B - half]])
        steps = run_group(static, sig, data, kernels)
        if fault == "state_unchanged":          # every step the first
            return np.repeat(steps[:, :1], steps.shape[1], axis=1)
        steps = steps.copy()                    # answer_altered
        steps[:, warmup, 0] *= 1.5
        return steps

    monkeypatch.setattr(jnp_engine, "_run_group", broken)
    out = run_small(name, seed=11)
    assert out["correct"] is False
    assert out["checks"]["worst_rel_iter"]["value"] \
        > out["checks"]["worst_rel_iter"]["limit"]
