"""The checks that decide ``correct`` fail what they must, at test scale.

* The control, the plain reference computed in bfloat16 (the precision
  below the float32 the configurations state), reads above every cell's
  limit.
* A run whose timed path is broken underneath, with the harness's look
  for a chip skipped, comes out not correct: once per fault the cells
  can have, and, in a cell with a paced job, once per pacing fault.
"""
import numpy as np
import pytest

from bench import cells, check
from bench.test_harness import CELLS, run_small, shrink

PACED = [n for n in CELLS
         if any(j["pacing"] for j in
                cells.cell(n)["config_data"]["scenario"]["jobs"])]


@pytest.mark.parametrize("name", CELLS)
def test_control_reads_above_the_limit(name):
    spec = cells.cell(name)
    scn, _ = shrink(spec["config_data"]["scenario"], spec["traffic_data"])
    scn["iters"] = 40
    limits = spec["limits"]
    worst = dict.fromkeys(check.NUMBERS, 0.0)
    for seed in (3, 4, 5):
        scn["base_seed"] = seed
        want, _, span = check.reference_span(scn, limits["departure"])
        assert span > 0
        got = check.control_rows(scn, span)
        for k, read in check.NUMBERS.items():
            worst[k] = max(worst[k], read(got, want))
    assert any(worst[k] > limit for k, limit in limits["limits"].items()
               if k in worst)


FAULTS = ("state_unchanged", "half_batch", "answer_altered")


@pytest.mark.parametrize("fault", FAULTS)
@pytest.mark.parametrize("name", CELLS)
def test_broken_timed_path_is_not_correct(name, fault, monkeypatch):
    from repro.fabric.backend import jnp_engine
    run_group = jnp_engine._run_group
    spec = cells.cell(name)
    warmup = shrink(spec["config_data"]["scenario"],
                    spec["traffic_data"])[0]["warmup"]

    def broken(static, sig, data, kernels):
        if fault == "half_batch":               # half the variants run;
            B = len(next(iter(data.values())))  # the rest copy them
            half = (B + 1) // 2
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
    assert any(c["value"] > c["limit"] for k, c in out["checks"].items()
               if k != "failed_variants")


# Removing the max_delay_frac bound is no fault these cells can show: in
# the paced cell the held delay never reaches it. A bound a tenth of the
# stated one is. A bank that goes wrong only halfway through the run
# stays within the widest gap's limit and is off for too long.
PACING_FAULTS = ("zero_delays", "tight_bound", "late_zero")


@pytest.mark.parametrize("fault", PACING_FAULTS)
@pytest.mark.parametrize("name", PACED)
def test_broken_pacing_is_not_correct(name, fault, monkeypatch):
    import jax.numpy as jnp
    from repro.fabric.backend import jnp_engine, jnp_kernels
    decide = jnp_kernels.bank_decide
    spec = cells.cell(name)
    half = shrink(spec["config_data"]["scenario"],
                  spec["traffic_data"])[0]["iters"] // 2

    def broken(*args, max_delay_frac, seen, **kw):
        if fault == "tight_bound":
            return decide(*args, max_delay_frac=max_delay_frac / 10,
                          seen=seen, **kw)
        delays, held = decide(*args, max_delay_frac=max_delay_frac,
                              seen=seen, **kw)
        if fault == "late_zero":
            return jnp.where(seen > half, 0.0, delays), held
        return jnp.zeros_like(delays), held

    monkeypatch.setattr(jnp_kernels, "bank_decide", broken)
    monkeypatch.setattr(jnp_engine, "_RUNNERS", {})   # traced anew
    out = run_small(name, seed=11)
    assert out["correct"] is False
    assert any(c["value"] > c["limit"] for k, c in out["checks"].items()
               if k != "failed_variants")
