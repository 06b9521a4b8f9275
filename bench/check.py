"""The comparison that decides ``correct``.

A checked variant's step series, as the timed sweep returned it, is held
against the plain reference (:mod:`bench.reference`, float64) of the same
scenario, iteration by iteration and tenant by tenant, as a relative gap
``|program - reference| / reference``.

How far that can hold is set by the reference itself. Where co-tenants
contend, the simulated dynamics amplify rounding: the reference run
against its twin, the same scenario with ``u_mean`` moved by one float32
epsilon, departs by more than ``departure`` at some iteration (the
horizon) and never returns. No float32 program can follow the reference
past it. So each variant is compared over the first half of its twin's
horizon, and over the whole run where the twin never departs.
"""
from __future__ import annotations

import copy
import itertools
from typing import Optional

import numpy as np

from bench import reference

EPS32 = float(np.finfo(np.float32).eps)


def twin(scn: dict) -> dict:
    out = copy.deepcopy(scn)
    out["congestion"]["u_mean"] = scn["congestion"]["u_mean"] * (1.0 + EPS32)
    return out


def reference_span(scn: dict, departure: float):
    """``(reference rows, horizon, span)``: the reference's reported rows
    over the checked span, the first reported iteration at which the twin
    departs (the run length if it never does), and the span checked."""
    warm, n = scn["warmup"], scn["iters"] - scn["warmup"]
    rows, horizon = [], n
    pairs = zip(reference.steps(scn), reference.steps(twin(scn)))
    for a, b in itertools.islice(pairs, warm, warm + n):
        if (np.abs(b - a) > departure * a).any():
            horizon = len(rows)
            break
        rows.append(a)
    span = n if horizon == n else horizon // 2
    return np.array(rows[:span]).reshape(span, -1), horizon, span


def control_rows(scn: dict, span: int) -> np.ndarray:
    """The control: the reference in bfloat16, its first ``span`` rows."""
    steps = reference.steps(scn, quantize=reference.bfloat16)
    rows = list(itertools.islice(steps, scn["warmup"],
                                 scn["warmup"] + span))
    return np.array(rows).reshape(span, -1)


def worst_rel(got: Optional[np.ndarray], want: np.ndarray) -> float:
    """Widest relative gap of ``got`` against ``want`` over ``want``'s
    rows; ``inf`` where ``got`` is missing, short or not finite."""
    span = want.shape[0]
    if got is None or got.ndim != 2 or got.shape[0] < span \
            or got.shape[1] != want.shape[1]:
        return float("inf")
    got = got[:span]
    if not np.isfinite(got).all():
        return float("inf")
    if span == 0:
        return 0.0
    return float((np.abs(got - want) / want).max())


def compare(scn: dict, got: Optional[np.ndarray], departure: float
            ) -> dict:
    """One checked variant: its twin horizon, the span checked, and the
    program's widest relative gap over it."""
    want, horizon, span = reference_span(scn, departure)
    return {"horizon": horizon, "span": span,
            "worst_rel_iter": worst_rel(got, want)}
